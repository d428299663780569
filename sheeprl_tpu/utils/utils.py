"""Core math / training utilities (jax).

TPU-native re-implementations of reference sheeprl/utils/utils.py:
- gae:64 -> reverse ``lax.scan`` (single fused XLA loop instead of a python
  time loop);
- symlog:150 / symexp:154, two_hot_encoder:158 / two_hot_decoder:183;
- polynomial_decay:135, normalize_tensor:122;
- Ratio:261 (host-side replay-ratio scheduler, identical semantics);
- dotdict:34 lives in sheeprl_tpu.config.compose.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.config.compose import dotdict  # noqa: F401  (re-export)

# numpy <-> jax dtype maps (reference utils/utils.py:18-33)
NUMPY_TO_JAX_DTYPE = {
    np.dtype("bool"): jnp.bool_,
    np.dtype("uint8"): jnp.uint8,
    np.dtype("int8"): jnp.int8,
    np.dtype("int32"): jnp.int32,
    np.dtype("int64"): jnp.int32,  # TPU has no int64 by default
    np.dtype("float16"): jnp.float16,
    np.dtype("float32"): jnp.float32,
    np.dtype("float64"): jnp.float32,
}


def symlog(x: jax.Array) -> jax.Array:
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x: jax.Array) -> jax.Array:
    return jnp.sign(x) * (jnp.exp(jnp.abs(x)) - 1.0)


def two_hot_encoder(x: jax.Array, support_range: int = 300, num_buckets: Optional[int] = None) -> jax.Array:
    """Two-hot encode ``x`` over a uniform support (plain — the caller symlogs).

    Equivalent of reference utils/utils.py:158-180: support has
    ``num_buckets`` bins spanning ``[-support_range, support_range]``.
    Input shape (..., 1) -> output (..., num_buckets).
    """
    if num_buckets is None:
        num_buckets = support_range * 2 + 1
    # plain two-hot, no symlog: like the reference util, the symlog
    # compression is the caller's (TwoHotEncodingDistribution's) job.
    # the support is a uniform linspace, so the bracketing bin and its value
    # are closed-form — no (..., num_buckets) comparison broadcast and no
    # gathers (TPU gathers are slow; this op runs on every reward/value
    # target of every train step)
    x = jnp.clip(x, -support_range, support_range)
    step = (2.0 * support_range) / (num_buckets - 1)
    below = jnp.floor((x + support_range) / step).astype(jnp.int32)
    below = jnp.clip(below, 0, num_buckets - 1)
    above = jnp.clip(below + 1, 0, num_buckets - 1)
    sup_below = -support_range + below.astype(x.dtype) * step
    sup_above = -support_range + above.astype(x.dtype) * step
    equal = below == above
    dist_below = jnp.where(equal, 1.0, jnp.abs(sup_below - x))
    dist_above = jnp.where(equal, 1.0, jnp.abs(sup_above - x))
    total = dist_below + dist_above
    w_below = dist_above / total
    w_above = dist_below / total
    oh_below = jax.nn.one_hot(below.squeeze(-1), num_buckets) * w_below
    oh_above = jax.nn.one_hot(above.squeeze(-1), num_buckets) * w_above
    return oh_below + oh_above


def two_hot_decoder(probs: jax.Array, support_range: int) -> jax.Array:
    """Decode a two-hot distribution back to a scalar (..., 1); plain
    expectation over the support (no symexp — the caller's job)."""
    num_buckets = probs.shape[-1]
    support = jnp.linspace(-support_range, support_range, num_buckets)
    return (probs * support).sum(-1, keepdims=True)


def gae(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    next_value: jax.Array,
    gamma: float,
    gae_lambda: float,
) -> Tuple[jax.Array, jax.Array]:
    """Generalized advantage estimation over time-major inputs.

    ``rewards``/``values``/``dones``: (T, B, 1); ``next_value``: (B, 1).
    Returns (returns, advantages), both (T, B, 1).

    Reference: sheeprl/utils/utils.py:64-102 (python loop over T);
    here a reverse ``lax.scan`` so the whole thing is one XLA op.
    """
    # advantage accumulation always runs in f32: under bf16 compute
    # policies the critic emits bf16 values, and a bf16 scan carry both
    # loses precision and trips the carry-dtype check (the f32 rewards
    # promote the carry output to f32)
    values = values.astype(jnp.float32)
    next_value = next_value.astype(jnp.float32)
    rewards = rewards.astype(jnp.float32)
    not_done = 1.0 - dones.astype(jnp.float32)
    next_values = jnp.concatenate([values[1:], next_value[None]], axis=0)

    def step(lastgaelam, inp):
        rew, nd, val, next_val = inp
        delta = rew + gamma * next_val * nd - val
        lastgaelam = delta + gamma * gae_lambda * nd * lastgaelam
        return lastgaelam, lastgaelam

    _, advantages = jax.lax.scan(
        step,
        jnp.zeros_like(next_value, dtype=jnp.float32),
        (rewards, not_done, values, next_values),
        reverse=True,
    )
    returns = advantages + values
    return returns, advantages


def lambda_values(
    rewards: jax.Array,
    values: jax.Array,
    continues: jax.Array,
    lmbda: float = 0.95,
) -> jax.Array:
    """TD(lambda) returns for Dreamer imagination rollouts.

    Inputs (T, B, 1) where ``continues`` already includes gamma.
    Reference: sheeprl/algos/dreamer_v3/utils.py:67-79.
    """
    # reference recursion: R[t] = r[t] + c[t]*((1-lambda)*v[t] + lambda*R[t+1])
    # seeded with R[T] = v[T-1] (UNshifted v[t] in the interm term — the
    # callers pass already-offset reward/value slices)
    interm = rewards + continues * values * (1 - lmbda)

    def step(carry, inp):
        it, cont = inp
        carry = it + cont * lmbda * carry
        return carry, carry

    # the recursion is a handful of elementwise ops over (B, 1) rows — full
    # unroll turns the whole return computation (fwd AND transpose/bwd) into
    # one fusion instead of a 15-trip while loop
    _, ret = jax.lax.scan(step, values[-1], (interm, continues), reverse=True, unroll=16)
    return ret


def normalize_tensor(x: jax.Array, eps: float = 1e-8, mask: Optional[jax.Array] = None) -> jax.Array:
    """(Optionally masked) standardization (reference utils/utils.py:122-133)."""
    if mask is None:
        return (x - x.mean()) / (x.std() + eps)
    m = mask.astype(x.dtype)
    n = m.sum()
    mean = (x * m).sum() / n
    var = (((x - mean) ** 2) * m).sum() / n
    return jnp.where(mask, (x - mean) / (jnp.sqrt(var) + eps), x)


def polynomial_decay(
    current_step: int,
    *,
    initial: float = 1.0,
    final: float = 0.0,
    max_decay_steps: int = 100,
    power: float = 1.0,
) -> float:
    """Host-side scheduler (reference utils/utils.py:135-147)."""
    if current_step > max_decay_steps or initial == final:
        return final
    return (initial - final) * ((1 - current_step / max_decay_steps) ** power) + final


def safetanh(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return jnp.clip(jnp.tanh(x), -1.0 + eps, 1.0 - eps)


def safeatanh(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return jnp.arctanh(jnp.clip(x, -1.0 + eps, 1.0 - eps))


class Ratio:
    """Replay-ratio scheduler: how many gradient steps to run per batch of
    new policy steps. Host-side, stateful, checkpointable — identical
    semantics to reference utils/utils.py:261-301 (from Hafner's dreamerv3).
    """

    def __init__(self, ratio: float, pretrain_steps: int = 0):
        if pretrain_steps < 0:
            raise ValueError(f"'pretrain_steps' must be non-negative, got {pretrain_steps}")
        if ratio < 0:
            raise ValueError(f"'ratio' must be non-negative, got {ratio}")
        self._pretrain_steps = pretrain_steps
        self._ratio = ratio
        self._prev: Optional[int] = None

    def __call__(self, step: int) -> int:
        if self._ratio == 0:
            return 0
        repeats = 0
        if self._prev is None:
            self._prev = step
            repeats = 1
            if self._pretrain_steps > 0:
                if step < self._pretrain_steps:
                    import warnings

                    warnings.warn(
                        "on the first step, more steps than pretrain_steps have already been done",
                        UserWarning,
                    )
                repeats = round(self._pretrain_steps * self._ratio)
        repeats += round((step - self._prev) * self._ratio)
        self._prev += repeats / self._ratio
        return int(repeats)

    def state_dict(self) -> Dict[str, Any]:
        return {"_ratio": self._ratio, "_prev": self._prev, "_pretrain_steps": self._pretrain_steps}

    def load_state_dict(self, state: Dict[str, Any]) -> "Ratio":
        self._ratio = state["_ratio"]
        self._prev = state["_prev"]
        self._pretrain_steps = state["_pretrain_steps"]
        return self


class MetricFetchGate:
    """Counts train dispatches and fires every ``metric.fetch_every``-th one
    (amortizes the device sync of the losses dict on high-latency links;
    1 = reference cadence). Counting dispatches rather than iterations keeps
    the gate aligned with whatever schedule the replay ratio produces.

    ``every > 1`` SUBSAMPLES: skipped dispatches' losses are dropped, not
    deferred, so logged averages cover every N-th dispatch (see
    configs/metric/default.yaml)."""

    def __init__(self, every: Any):
        self.every = max(1, int(every or 1))
        self._n = 0

    def __call__(self) -> bool:
        hit = self._n % self.every == 0
        self._n += 1
        return hit


def start_async_host_copy(*arrays: Any) -> None:
    """Kick off device-to-host copies without waiting for them.

    The env hot loop needs the (tiny) action array NOW but the logprob /
    value / flat-action arrays only after ``envs.step`` returns; starting
    their copies before the env step lets the transfers ride under the
    env's wall-clock instead of serializing ``np.asarray`` round trips
    afterwards.  No-op for leaves that are not device arrays (numpy
    inputs, already-fetched results)."""
    for a in arrays:
        fn = getattr(a, "copy_to_host_async", None)
        if fn is not None:
            try:
                fn()
            except RuntimeError:
                pass  # deleted/donated buffer: the later np.asarray will raise


def fetch_actions(
    action_list: Sequence[jax.Array],
    actions_dim: Sequence[int],
    is_continuous: bool,
    num_envs: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Single device-to-host fetch of the player's per-head actions.

    Returns ``(actions, real_actions)``: the flat ``(1, num_envs,
    sum(actions_dim))`` buffer layout, and the env-facing form
    (concatenated floats for continuous spaces, per-head argmax indices
    for discrete/multi-discrete). Every ``np.asarray`` of a device array
    waits for the device and copies to the host, so the heads are
    concatenated on-device and fetched ONCE; everything else is derived
    host-side."""
    flat = np.asarray(jnp.concatenate(action_list, -1))
    actions = flat.reshape(1, num_envs, -1)
    if is_continuous:
        real_actions = flat
    else:
        segments = np.split(flat, np.cumsum(np.asarray(actions_dim))[:-1], axis=-1)
        real_actions = np.stack([seg.argmax(-1) for seg in segments], -1)
    return actions, real_actions


def device_get_metrics(metrics: Dict[str, Any]) -> Dict[str, float]:
    """Fetch a dict of device scalars with ONE device-to-host transfer.

    ``jax.device_get`` on a pytree copies leaf by leaf; on a remote
    accelerator each copy pays the full link latency, which turns a
    15-scalar metrics dict into seconds per training iteration. Stacking on
    device first (one eager op) makes it a single small transfer."""
    if not metrics:
        return {}
    scalars = {k: v for k, v in metrics.items() if int(np.prod(np.shape(v))) == 1}
    out: Dict[str, Any] = {}
    if scalars:
        keys = list(scalars)
        vals = np.asarray(jnp.stack([jnp.asarray(scalars[k]).reshape(()) for k in keys]))
        out.update({k: float(v) for k, v in zip(keys, vals)})
    for k, v in metrics.items():  # non-scalar metrics keep their full value
        if k not in out:
            # the leftover NON-scalar metrics; the scalars above already
            # rode the one batched fetch
            # jaxlint: disable-next=host-sync
            out[k] = jax.device_get(v)
    return out


def transfer_tree(tree: Any, device) -> Any:
    """Move a pytree to ``device`` with at most ONE cross-backend copy.

    ``jax.device_put`` on a pytree that has to leave the accelerator copies
    leaf by leaf; on a remote accelerator every leaf pays the full link
    latency, which turns a 200-leaf params tree into minutes. Here the
    leaves are raveled and concatenated ON the source device (async eager
    ops), fetched in one D2H copy, and re-split host-side before the cheap
    host->device placement."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves or device is None:
        return tree if device is None else jax.device_put(tree, device)

    # Partition by ACTUAL leaf location: only leaves living on a remote
    # accelerator need the concat-and-single-fetch path.  Host (numpy) and
    # same-platform leaves go straight through device_put — routing them
    # through jnp.concatenate would first PUSH them to the remote source
    # device and fetch them back, extra round trips on exactly the
    # high-latency links this function optimizes.
    target_platform = getattr(device, "platform", None)
    out = [None] * len(leaves)
    remote = []
    for i, leaf in enumerate(leaves):
        src = next(iter(leaf.devices())) if hasattr(leaf, "devices") else None
        if src is None or src.platform == target_platform:
            out[i] = jax.device_put(leaf, device)
        else:
            remote.append(i)
    # one transfer per dtype group — NO casting, so integer/f64 leaves stay
    # exact and bf16 leaves don't double their payload
    groups: Dict[Any, list] = {}
    for i in remote:
        groups.setdefault(jnp.asarray(leaves[i]).dtype, []).append(i)
    for dt, idxs in groups.items():
        flat = jnp.concatenate([jnp.ravel(leaves[i]) for i in idxs])
        # this IS the designed single cross-backend copy per dtype group
        # (see docstring)
        # jaxlint: disable-next=host-sync
        host = np.asarray(flat)  # the single cross-backend copy per dtype
        off = 0
        for i in idxs:
            n = int(np.prod(leaves[i].shape))
            out[i] = jax.device_put(host[off : off + n].reshape(leaves[i].shape), device)
            off += n
    return jax.tree_util.tree_unflatten(treedef, out)


# bytes the players' weight refreshes copied across backends since the last
# telemetry record took them (the record's ``player.refresh_copied_bytes``)
_refresh_copied_bytes = 0


def place_player_params(tree: Any, device) -> Any:
    """What a player's ``params`` setter does with the tree it is given:
    ``device=None`` (the player acts on the training device) keeps the
    learner's own arrays, by reference; any other device gets a copy through
    :func:`transfer_tree`, and the bytes that cross backends are counted."""
    global _refresh_copied_bytes
    if device is not None:
        _refresh_copied_bytes += sum(
            leaf.nbytes
            for leaf in jax.tree_util.tree_leaves(tree)
            if hasattr(leaf, "devices") and next(iter(leaf.devices())).platform != device.platform
        )
    return transfer_tree(tree, device)


def take_refresh_copied_bytes() -> int:
    """The count above, read and reset (once per telemetry record)."""
    global _refresh_copied_bytes
    taken, _refresh_copied_bytes = _refresh_copied_bytes, 0
    return taken


def save_configs(cfg: dotdict, log_dir: str) -> None:
    """Persist the resolved run config next to the logs (utils/utils.py:257)."""
    import yaml

    os.makedirs(log_dir, exist_ok=True)
    with open(os.path.join(log_dir, "config.yaml"), "w") as f:
        yaml.safe_dump(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg), f)


def print_config(cfg: Any) -> None:
    """rank-0 rich tree dump of the run config (utils/utils.py:211)."""
    try:
        import rich.tree
        import rich.syntax
        import yaml

        tree = rich.tree.Tree("CONFIG", style="dim", guide_style="dim")
        for k, v in cfg.items():
            branch = tree.add(str(k), style="yellow", guide_style="yellow")
            if isinstance(v, dict):
                branch.add(rich.syntax.Syntax(yaml.safe_dump(_plain(v)), "yaml"))
            else:
                branch.add(str(v))
        rich.print(tree)
    except Exception:
        pass


def _plain(v: Any) -> Any:
    if isinstance(v, dict):
        return {k: _plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    return v


# ------------------------------------------------------------------ #
# scan-body optimization knobs, shared by every Dreamer-family train fn
# (measured on DV3, see dreamer_v3.make_train_fn; the bodies are
# latency-bound so remat policy + unroll matter identically everywhere)
# ------------------------------------------------------------------ #
def scan_remat(f):
    """Wrap a scan body for a rematerialized backward pass that saves matmul
    results and recomputes the elementwise chains."""
    return jax.checkpoint(f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def scan_unroll_setting(cfg=None, kind: str = "dyn") -> int:
    """Unroll factor for the dynamic ("dyn") / imagination ("img") scans:
    cfg.algo.{scan_unroll,imagination_unroll}, else the measured default."""
    attr, default = ("imagination_unroll", 3) if kind == "img" else ("scan_unroll", 8)
    cfg_val = getattr(getattr(cfg, "algo", None), attr, None) if cfg is not None else None
    return int(cfg_val or default)
