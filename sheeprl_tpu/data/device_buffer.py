"""HBM-resident replay cache with on-device sequence sampling.

Why this exists (TPU-first design, no reference counterpart): the
reference's training loop re-reads every minibatch from a host-RAM buffer
(sheeprl dreamer_v3.py:628-641 samples torch tensors per gradient step).
A DV3-S batch (T=64, B=16 of 64x64x3 uint8) is 12.6 MB: sampling it on
the host and uploading it every gradient step puts host work and a
host->HBM copy on the critical path of a ~15 ms train step (the driver's
last record, BENCH_r05: 535.8 ms per gradient step from the host vs
6.02 ms from this cache).  So the replay window lives IN HBM: each policy
step uploads only the new frames (n_envs x ~12 KB), and sampling becomes
an on-device read that feeds the jitted train step with zero host
round-trips.

Semantics mirror ``EnvIndependentReplayBuffer`` over
``SequentialReplayBuffer`` (data/buffers.py:299,387): one ring per env
with an independent write head, env chosen uniformly per batch element,
sequence starts uniform over the valid wrap-around-safe window (never
crossing the write head), windows contiguous within a single env.  The
host buffer stays the source of truth for checkpointing — this cache is
derived state, rebuilt from the host buffer on resume
(:meth:`load_from`).

How a window is read (PERF.md §6, PR 25).  On a v5e XLA's default layout
of a ring ``u8[cap, n_envs, 64, 64, 3]`` is ``{0,3,4,2,1:T(8,128)(4,1)}``:
**capacity is the minor-most (lane) dimension**, then W, C, H, env (the
vector rings likewise, ``f32[cap, n_envs, 1]{0,2,1}``).  A two-index gather
``buf[t_idx, e_idx]`` wants the indexed dims major, so XLA first copies the
WHOLE ring to another layout: 26 ms and 4.18 GB of temporaries a draw for
a 2.09 GB ring and a 12.6 MB batch.  So each sequence window is read as a
contiguous ``lax.dynamic_slice`` along the capacity axis at ``(start, env)``,
in the ring's own layout, unrolled in Python over the batch
(:func:`_window_slice`; 0.5 ms).  The form matters — ``vmap`` of the slice
is a gather again, a ``fori_loop`` over the ring relays it again — and needs
no chip to check: compile for a described v5e and read the HLO, as
``tests/test_data/test_device_buffer_tpu_layout.py`` does
(``topologies.get_topology_desc("tpu", "v5e:2x2")``, arguments as
``ShapeDtypeStruct(..., sharding=SingleDeviceSharding(topo.devices[0]))``,
then ``_sample.lower(...).compile()``: ``as_text()`` must hold no copy of the
ring's shape, ``memory_analysis().temp_size_in_bytes`` a few MB).
A draw is one small index program (:func:`_sample_draw`) and one read
program per gradient step (:func:`_sample`), so the unrolled program grows
with ``batch x keys`` and never with ``n_samples``.  The flat-transition
samplers (SAC family) still use the two-index gather (PERF.md §7).

Gating: ``buffer.device_cache`` (True / False / "auto"; env override
``SHEEPRL_DEVICE_CACHE``).  "auto" enables on single-device accelerator
meshes when the estimated footprint fits ``buffer.device_cache_budget_gb``
(default 6.0).  Multi-host
data parallelism keeps the host path (each process feeds its own shard).
Single-process multi-device meshes route to
:class:`ShardedDeviceReplayCache` — env-sharded rings over the mesh batch
axes — when opted in (``device_cache=True``) or whenever
``buffer.prioritized`` needs the device sampler: uniform draws stay
device-local (stratified), prioritized ones run per-shard sum-trees with
one psum'd total-mass reduction per draw (howto/sharding.md), for both
the sequence and flat-transition buffer families.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from sheeprl_tpu.utils.timer import timer

__all__ = [
    "DeviceReplayCache",
    "ShardedDeviceReplayCache",
    "device_cache_setting",
    "maybe_create_for",
    "maybe_create_for_transitions",
    "sequence_batches",
]


# one ring array must stay gather-addressable with int32 linear offsets on
# TPU (2^31, with a 1 MiB margin); see DeviceReplayCache._ensure
_INT32_SAFE_BOUND = 2**31 - 2**20


def _store_dtype(dt) -> np.dtype:
    dt = np.dtype(dt)
    return np.dtype(np.float32) if dt == np.float64 else dt


def device_cache_setting(cfg) -> str:
    """Resolve ``buffer.device_cache`` with its env override to one of
    "on" / "off" / "auto"."""
    val = cfg.buffer.get("device_cache", "auto")
    env = os.environ.get("SHEEPRL_DEVICE_CACHE")
    if env is not None:
        val = env
    s = str(val).lower()
    if s in ("1", "true", "on", "yes"):
        return "on"
    if s in ("0", "false", "off", "no"):
        return "off"
    return "auto"


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("n_envs",))
def _append(bufs, row, pos, mask, *, n_envs):
    """Write one row per env at its own ring position, where mask says so.

    bufs: {k: (cap, n_envs, *feat)}; row: {k: (n_envs, *feat)};
    pos (n_envs,) i32 write heads; mask (n_envs,) bool.
    """
    envs = jnp.arange(n_envs)
    out = {}
    for k, buf in bufs.items():
        cur = buf[pos, envs]  # (n_envs, *feat)
        m = mask.reshape((n_envs,) + (1,) * (cur.ndim - 1))
        new = jnp.where(m, row[k].astype(buf.dtype), cur)
        out[k] = buf.at[pos, envs].set(new)
    return out


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("n_envs",))
def _append_window(bufs, block, pos, mask, valid, *, n_envs):
    """Write T consecutive rows per env starting at its ring position.

    bufs: {k: (cap, n_envs, *feat)}; block: {k: (T, n_envs, *feat)};
    pos (n_envs,) i32 write heads; mask (n_envs,) bool; valid (T,) bool —
    rows with ``valid[t]`` False are padding and leave the ring untouched.
    One dispatch for the whole window: the per-row path costs one jit
    dispatch + H2D per env step, which on a remote link dominates an
    off-policy algo's steady state once training itself is
    dispatch-batched.  Callers pad every window to a FIXED length with the
    tail masked off (see :meth:`DeviceReplayCache.add`), so only one or
    two window shapes ever trace — per-length retraces used to recompile
    this kernel for every distinct flush length (ADVICE r5).
    """
    t_len = next(iter(block.values())).shape[0]
    cap = next(iter(bufs.values())).shape[0]
    envs = jnp.arange(n_envs)

    def body(t, bufs):
        p = (pos + t) % cap
        row_mask = jnp.logical_and(mask, valid[t])
        out = {}
        for k, buf in bufs.items():
            cur = buf[p, envs]
            m = row_mask.reshape((n_envs,) + (1,) * (cur.ndim - 1))
            row = jax.lax.dynamic_index_in_dim(block[k], t, 0, keepdims=False)
            out[k] = buf.at[p, envs].set(jnp.where(m, row.astype(buf.dtype), cur))
        return out

    return jax.lax.fori_loop(0, t_len, body, bufs)


def _transition_window(pos, filled, *, cap, next_keys):
    """Masked index space shared by the flat-transition samplers: the
    oldest stored row (``base``) and the count of sampleable rows — the
    row at the write head is excluded when next-obs are gathered (its
    successor is stale).  SAC-family buffers add all envs in lockstep, so
    pos/filled are shared scalars (element 0 of the per-env vectors).
    Hoisted so the uniform and prioritized samplers agree on validity by
    construction instead of forking the mask logic."""
    p0 = pos[0]
    f0 = filled[0]
    count = f0 - (1 if next_keys else 0)
    base = jnp.where(f0 >= cap, p0, 0)
    return base, count


def _gather_transitions(bufs, rows, envs, *, n_samples, batch_size, cap, next_keys):
    """Flat-transition gather shared by the uniform and prioritized
    samplers: (flat,) row/env indices -> (n_samples, batch, *feat) dicts,
    next row = (row + 1) % cap for ``next_keys``."""
    out = {}
    for k, buf in bufs.items():
        g = buf[rows, envs]  # (flat, *feat)
        out[k] = g.reshape(n_samples, batch_size, *buf.shape[2:])
    if next_keys:  # jaxlint: disable=retrace-branch — static obs-key tuple, not a tracer
        nrows = (rows + 1) % cap
        for k in next_keys:
            g = bufs[k][nrows, envs]
            out[f"next_{k}"] = g.reshape(n_samples, batch_size, *bufs[k].shape[2:])
    return out


@functools.partial(
    jax.jit,
    static_argnames=("n_samples", "batch_size", "cap", "n_envs", "next_keys"),
)
def _sample_transitions(bufs, key, pos, filled, *, n_samples, batch_size, cap, n_envs, next_keys):
    """Gather (n_samples, batch, *feat) flat transitions, mirroring
    ``ReplayBuffer.sample``: rows uniform over stored history, env uniform
    per element (see :func:`_transition_window` for the validity mask)."""
    flat = n_samples * batch_size
    k_env, k_row = jax.random.split(key)
    envs = jax.random.randint(k_env, (flat,), 0, n_envs)
    base, count = _transition_window(pos, filled, cap=cap, next_keys=next_keys)
    u = jax.random.uniform(k_row, (flat,))
    offs = jnp.minimum((u * count).astype(jnp.int32), count - 1)
    rows = (base + offs) % cap
    return _gather_transitions(
        bufs, rows, envs, n_samples=n_samples, batch_size=batch_size, cap=cap,
        next_keys=next_keys,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_samples", "batch_size", "cap", "n_envs", "next_keys", "depth"),
)
def _sample_transitions_prioritized(
    bufs, tree, key, pos, filled, beta, *, n_samples, batch_size, cap, n_envs, next_keys, depth
):
    """Proportional prioritized counterpart of :func:`_sample_transitions`:
    (row, env) cells drawn from the sum-tree (leaf = row * n_envs + env),
    validity by construction — unwritten cells carry zero priority, and
    the per-env write-head row is zeroed in a functional tree copy when
    next-obs are gathered (same exclusion as :func:`_transition_window`).
    Returns the batch dict + ``is_weights`` (β-annealed, batch-max
    normalized) and the sampled leaf indices for ``update_priorities``."""
    from sheeprl_tpu.replay.priority_tree import _tree_sample, _tree_zeroed

    flat = n_samples * batch_size
    # live-cell count N for the IS correction w = (N * P(i))^-beta
    n_live = jnp.sum(filled) - (n_envs if next_keys else 0)
    t = tree
    if next_keys:  # jaxlint: disable=retrace-branch — static obs-key tuple, not a tracer
        head_rows = (pos - 1) % cap  # per-env newest row: its successor is stale
        head_leaves = head_rows * n_envs + jnp.arange(n_envs)
        t = _tree_zeroed(t, head_leaves, jnp.ones((n_envs,), bool), depth=depth)
    leaves, w = _tree_sample(t, key, beta, n_live, n=flat, depth=depth)
    rows = leaves // n_envs
    envs = leaves % n_envs
    out = _gather_transitions(
        bufs, rows, envs, n_samples=n_samples, batch_size=batch_size, cap=cap,
        next_keys=next_keys,
    )
    out["is_weights"] = w.reshape(n_samples, batch_size, 1)
    return out, leaves.reshape(n_samples, batch_size)


def _draw_windows(key, pos, filled, *, flat, seq_len, cap, n_envs):
    """Uniform index draw shared by the single-device jit and the per-device
    body of the sharded sampler: ``flat`` (start, env) pairs.

    Valid starts per env mirror SequentialReplayBuffer.sample: the stored
    rows span logical times [pos - filled, pos); any L-window inside that
    span is valid, i.e. ``filled - L + 1`` starts beginning at the oldest
    row (ring index ``pos`` when full, 0 otherwise).
    """
    k_env, k_start = jax.random.split(key)
    envs = jax.random.randint(k_env, (flat,), 0, n_envs)
    counts = filled - seq_len + 1  # (n_envs,) — caller guarantees >= 1
    base = jnp.where(filled >= cap, pos, 0)
    c_e = counts[envs]
    u = jax.random.uniform(k_start, (flat,))
    offs = jnp.minimum((u * c_e).astype(jnp.int32), c_e - 1)
    starts = (base[envs] + offs) % cap
    return starts, envs


def _rows(flat_idx, n_samples):
    """(n_samples * B,) -> n_samples arrays of (B,): one per gradient step,
    so the window reader never sees ``n_samples``."""
    return tuple(flat_idx.reshape(n_samples, -1))


def _window_slice(buf, start, env, *, seq_len):
    """One (L, *feat) window of env ``env`` starting at ring row ``start``,
    read as contiguous slices along the capacity axis (module docstring)."""
    cap = buf.shape[0]

    def rows_from(row):  # (L, 1, *feat), in the ring's own layout
        return jax.lax.dynamic_slice(buf, (row, env) + (0,) * (buf.ndim - 2), (seq_len, 1) + buf.shape[2:])

    s0 = jnp.minimum(start, cap - seq_len)  # dynamic_slice would clamp anyway
    # the ring's head follows: a window that wraps past cap - 1 continues there
    both = jnp.concatenate([rows_from(s0), rows_from(0)], axis=0)
    return jax.lax.dynamic_slice_in_dim(both, start - s0, seq_len, axis=0)[:, 0]


def _read_windows(bufs, starts, envs, *, seq_len):
    """(B,) starts/envs -> {k: (L, B, *feat)} — the shared tail of the
    uniform and prioritized sequence samplers, one gradient step's batch.

    Unrolled in Python on purpose: ``vmap`` of a dynamic slice is a gather
    again, and a ``fori_loop`` over the ring relays it again.  Small vector
    rings take the same path: on a v5e their 80 slices cost less than the
    gather they replace (0.05 against 0.12 ms at 42,500 x 4)."""
    return {
        k: jnp.stack(
            [_window_slice(buf, starts[i], envs[i], seq_len=seq_len) for i in range(starts.shape[0])],
            axis=1,
        )
        for k, buf in bufs.items()
    }


@functools.partial(
    jax.jit, static_argnames=("n_samples", "batch_size", "seq_len", "cap", "n_envs")
)
def _sample_draw(key, pos, filled, *, n_samples, batch_size, seq_len, cap, n_envs):
    """The whole index draw of one ``sample`` call, cut per gradient step."""
    starts, envs = _draw_windows(
        key, pos, filled, flat=n_samples * batch_size, seq_len=seq_len, cap=cap, n_envs=n_envs
    )
    return _rows(starts, n_samples), _rows(envs, n_samples)


@functools.partial(jax.jit, static_argnames=("seq_len",))
def _sample(bufs, starts, envs, *, seq_len):
    """Read one gradient step's (seq_len, batch, *feat) sequence windows."""
    return _read_windows(bufs, starts, envs, seq_len=seq_len)


@functools.partial(
    jax.jit,
    static_argnames=("n_samples", "batch_size", "seq_len", "cap", "n_envs", "depth"),
)
def _sample_draw_prioritized(
    tree, key, pos, filled, beta, *, n_samples, batch_size, seq_len, cap, n_envs, depth
):
    """Prioritized sequence-START sampling (Dreamer family, behind
    ``buffer.prioritized``): window starts drawn proportional to their
    cell's priority instead of uniformly.  Validity matches
    :func:`_draw_windows` exactly — the L-1 rows immediately preceding
    each env's write head cannot start a full window (zeroed in a
    functional tree copy).
    Returns the per-step (starts, envs) rows for :func:`_sample` + the
    sampled start leaves (the caller may decay them — recency-biased
    replay without a TD signal)."""
    from sheeprl_tpu.replay.priority_tree import _tree_sample, _tree_zeroed

    flat = n_samples * batch_size
    n_live = jnp.sum(jnp.maximum(filled - seq_len + 1, 0))
    inv_leaves = None
    if seq_len > 1:  # jaxlint: disable=retrace-branch — static (python int) window length
        offs = jnp.arange(1, seq_len)  # (L-1,)
        inv_rows = (pos[None, :] - offs[:, None]) % cap  # (L-1, n_envs)
        inv_leaves = (inv_rows * n_envs + jnp.arange(n_envs)[None, :]).reshape(-1)
    t = tree
    if inv_leaves is not None:
        t = _tree_zeroed(t, inv_leaves, jnp.ones(inv_leaves.shape, bool), depth=depth)
    leaves, _w = _tree_sample(t, key, beta, n_live, n=flat, depth=depth)
    return _rows(leaves // n_envs, n_samples), _rows(leaves % n_envs, n_samples), leaves


@contextlib.contextmanager
def sequence_batches(rb, device_cache, runtime, n_samples, batch_size, seq_len, key, **sample_kwargs):
    """Uniform train-loop feed: yields an iterable of per-gradient-step
    batch dicts — an on-device gather when the cache is usable, else the
    host ``rb.sample`` + ``batched_feed`` prefetch path.  Call OUTSIDE the
    train timer so host sampling keeps its historical accounting.
    ``sample_kwargs`` (e.g. DV2's prioritize_ends) go to the host sampler;
    the cache path only exists for plain sequential buffers, where they
    are no-ops.

    ``Time/feed_dispatch`` times what the host does before the first batch
    is handed out: the sampler's dispatch on the ring path, ``rb.sample``
    and the prefetcher's set-up on the host path (its uploads then overlap
    the train timer)."""
    if device_cache is not None and device_cache.can_sample(seq_len):
        with timer("Time/feed_dispatch"):
            if getattr(device_cache, "prioritized", False) and device_cache._tree is not None:
                # prioritized sequence-START sampling (Dreamer family): biased
                # by design like DV2's prioritize_ends — no IS reweighting of
                # the world-model losses, so β is irrelevant here
                batches = device_cache.sample_per(n_samples, batch_size, seq_len, key, beta=0.0)
            else:
                batches = device_cache.sample(n_samples, batch_size, seq_len, key)
        yield batches
        return
    from sheeprl_tpu.data.feed import batched_feed

    with contextlib.ExitStack() as stack:
        with timer("Time/feed_dispatch"):
            local_data = rb.sample(
                batch_size, sequence_length=seq_len, n_samples=n_samples, **sample_kwargs
            )
            feed = stack.enter_context(
                batched_feed(local_data, n_samples, sharding=runtime.batch_sharding(axis=1))
            )
        yield feed


def maybe_create_for_transitions(cfg, runtime, rb, state=None):
    """SAC-family factory: a cache mirroring a plain flat-transition
    ``ReplayBuffer`` (uniform rows, optional next-obs).  Pass ``state`` iff
    ``rb`` was restored — the cache refills from it."""
    from sheeprl_tpu.data.buffers import ReplayBuffer

    if type(rb) is not ReplayBuffer:
        return None
    cache = DeviceReplayCache.maybe_create(
        cfg, runtime, capacity=rb.buffer_size, n_envs=rb.n_envs
    )
    if cache is None:
        # multi-device: the env-sharded cache keeps transitions (and the
        # PER sum-trees) on the mesh — uniform draws stay device-local,
        # prioritized ones pay one psum'd mass reduction per draw
        cache = _maybe_create_sharded(cfg, runtime, rb.buffer_size, rb.n_envs)
    if cache is not None and state is not None:
        cache.load_from_replay(rb)
        if cache.prioritized:
            cache.load_priority_state(state.get("replay_priority"))
    return cache


def _maybe_create_sharded(cfg, runtime, capacity: int, n_envs: int):
    """Shared multi-device gating for both buffer families: the env-sharded
    cache applies on single-process multi-device meshes when explicitly
    opted in (``buffer.device_cache=True``) OR when ``buffer.prioritized``
    requires the device sampler (the sum-trees live with the cache —
    there is no host PER path to fall back to, so blockers are a hard
    config error rather than a silent uniform downgrade)."""
    mode = device_cache_setting(cfg)
    prioritized = bool(cfg.buffer.get("prioritized", False))
    if runtime.device_count <= 1:
        return None
    if mode == "off" or not (mode == "on" or prioritized):
        return None
    blockers = []
    if jax.process_count() != 1:
        blockers.append("multi-process run (each process feeds its own shard)")
    if n_envs % runtime.device_count:
        blockers.append(f"n_envs ({n_envs}) not divisible by {runtime.device_count} devices")
    if blockers:
        if prioritized:
            # PER without the device sampler would silently train on a
            # different (uniform) distribution — refuse loudly instead
            raise ValueError(
                "buffer.prioritized=True needs the env-sharded device cache on a "
                "multi-device mesh, which this run cannot build: " + "; ".join(blockers)
            )
        print(
            "DeviceReplayCache: buffer.device_cache=True ignored — "
            + "; ".join(blockers)
            + "; keeping the host feed path"
        )
        return None
    cache = ShardedDeviceReplayCache(
        capacity,
        n_envs,
        runtime,
        prioritized=prioritized,
        per_alpha=float(cfg.buffer.get("per_alpha", 0.6)),
        per_eps=float(cfg.buffer.get("per_eps", 1e-6)),
        per_decay=cfg.buffer.get("per_decay_on_sample", None),
    )
    print(
        f"DeviceReplayCache: env-sharded replay window enabled "
        f"(capacity {capacity} x {n_envs} envs over "
        f"{runtime.device_count} devices"
        + (", prioritized per-shard sum-trees" if prioritized else "")
        + ")"
    )
    return cache


def maybe_create_for(cfg, runtime, rb, state=None):
    """One-line factory for the training loops: a cache mirroring ``rb``
    when it is an EnvIndependentReplayBuffer and gating allows (EpisodeBuffer
    replay — DV2's prioritize_ends mode — keeps the host path).  Pass
    ``state`` iff ``rb`` was restored from a checkpoint — the cache then
    refills from it (a non-restored rb is empty, so the refill is a no-op
    either way; the flag just documents intent at the call sites)."""
    from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer

    if not isinstance(rb, EnvIndependentReplayBuffer):
        return None
    cache = DeviceReplayCache.maybe_create(
        cfg, runtime, capacity=rb.buffer_size, n_envs=rb.n_envs
    )
    if cache is None:
        cache = _maybe_create_sharded(cfg, runtime, rb.buffer_size, rb.n_envs)
    if cache is not None and state is not None:
        cache.load_from(rb)
        if cache.prioritized:
            cache.load_priority_state(state.get("replay_priority"))
    return cache


class DeviceReplayCache:
    """Device mirror of a sequential replay buffer (see module docstring).

    Created lazily on the first :meth:`add` (dtypes/shapes come from the
    first ``step_data`` row).  All arrays live on ``device`` (the runtime's
    training device); appends donate the buffers so updates are in-place.
    """

    def __init__(
        self,
        capacity: int,
        n_envs: int,
        device=None,
        budget_bytes: Optional[int] = None,
        prioritized: bool = False,
        per_alpha: float = 0.6,
        per_eps: float = 1e-6,
        per_decay: Optional[float] = None,
    ):
        if capacity <= 0 or n_envs <= 0:
            raise ValueError(f"capacity ({capacity}) and n_envs ({n_envs}) must be positive")
        self.capacity = int(capacity)
        self.n_envs = int(n_envs)
        self._device = device
        self._budget = budget_bytes
        # prioritized replay (Schaul et al., 2016): a device sum-tree over
        # the (row, env) cells rides next to the rings; False keeps the
        # uniform samplers untouched (bit-exact with the pre-PER code)
        self.prioritized = bool(prioritized)
        self.per_alpha = float(per_alpha)
        self.per_eps = float(per_eps)
        self.per_decay = per_decay if per_decay is None else float(per_decay)
        self._tree = None
        self._bufs: Optional[Dict[str, jax.Array]] = None
        self._pos = np.zeros(n_envs, dtype=np.int32)
        self._filled = np.zeros(n_envs, dtype=np.int32)
        # fixed dispatch length for windowed appends: the first windowed
        # add sets it and every later window is padded (masked tail) or
        # grows it, so _append_window traces at most one or two shapes
        # instead of one per distinct flush length
        self._window_pad: Optional[int] = None
        self.active = True  # flips False if the first row busts the budget

    # ------------------------------------------------------------- admin
    def estimate_bytes(self, row: Dict[str, np.ndarray]) -> int:
        total = 0
        for v in row.values():
            feat = v.shape[2:]
            total += (
                self.capacity
                * self.n_envs
                * int(np.prod(feat, dtype=np.int64) or 1)
                * _store_dtype(v.dtype).itemsize
            )
        return total

    def _per_device_envs(self) -> int:
        """Env count addressed by one device's gather (the sharded subclass
        holds 1/n_dev of the env axis per device)."""
        return self.n_envs

    def _admit(self, row: Dict[str, np.ndarray]) -> bool:
        """Size gates shared by the fresh-run (`_ensure`) and resume
        (`load_from*`) allocation paths.  Flips ``active`` off (host feed
        path) instead of erroring."""
        if self._budget is not None:
            est = self.estimate_bytes(row)
            if est > self._budget:
                self.active = False
                print(
                    f"DeviceReplayCache: estimated {est / 1e9:.2f} GB exceeds the "
                    f"{self._budget / 1e9:.2f} GB budget — staying on the host path"
                )
                return False
        for k, v in row.items():
            feat_elems = int(np.prod(v.shape[2:], dtype=np.int64) or 1)
            nbytes = (
                self.capacity
                * self._per_device_envs()
                * feat_elems
                * _store_dtype(v.dtype).itemsize
            )
            # int32-addressability gate: the window/transition gathers index
            # one (capacity, n_envs, *feat) array and XLA's TPU gather
            # lowering linearizes offsets in int32 — past 2^31 the address
            # math overflows and CRASHES the TPU worker.  Bytes always
            # dominate elements (itemsize >= 1), so bytes are the check.
            # Below it no other ring-size gate is needed: a 2.09 GB pixel
            # ring (97 % of the bound) ran 673 interleaved append / sample /
            # train dispatches over its whole address range clean on a v5e
            # (PR 21).
            if nbytes > _INT32_SAFE_BOUND:
                self.active = False
                print(
                    f"DeviceReplayCache: array '{k}' ring would be {nbytes / 1e9:.2f} GB "
                    f"— beyond int32-safe gather addressing (2^31 bytes); staying on "
                    f"the host path (shrink buffer.size to enable)"
                )
                return False
        return True

    def _ensure(self, row: Dict[str, np.ndarray]) -> bool:
        if self._bufs is not None:
            return True
        if not self.active:
            return False
        if not self._admit(row):
            return False
        self._bufs = {
            # f64 host rows (numpy default zeros) store as f32 — the
            # train steps consume f32 anyway (mirrors batched_feed)
            k: self._zeros((self.capacity, self.n_envs, *v.shape[2:]), _store_dtype(v.dtype))
            for k, v in row.items()
        }
        self._ensure_tree()
        return True

    def _ensure_tree(self) -> None:
        if self.prioritized and self._tree is None:
            from sheeprl_tpu.replay.priority_tree import PriorityTree

            self._tree = PriorityTree(
                self.capacity * self.n_envs,
                alpha=self.per_alpha,
                eps=self.per_eps,
                device=self._device,
            )

    def _seed_tree_window(
        self, start: np.ndarray, t_len: int, mask_np: np.ndarray, valid: Optional[np.ndarray] = None
    ) -> None:
        """Priority-seed the cells just written (max-priority insert,
        Schaul §3.3) — also what keeps ring OVERWRITE correct: the evicted
        transition's stale priority is replaced, never sampled again.
        ``valid`` mirrors the padded windowed append (padding rows leave
        the tree untouched, and the pad keeps this write's trace count
        matching ``_append_window``'s)."""
        if self._tree is None:
            return
        rows = (start[None, :] + np.arange(t_len)[:, None]) % self.capacity  # (T, n_envs)
        leaves = rows * self.n_envs + np.arange(self.n_envs)[None, :]
        active = np.broadcast_to(mask_np[None, :], leaves.shape)
        if valid is not None:
            active = active & valid[:, None]
        self._tree.seed_max(leaves.reshape(-1), np.ascontiguousarray(active).reshape(-1))

    # ---- array-placement hooks (the sharded subclass overrides ONLY these)
    def _zeros(self, shape, dtype):
        with jax.default_device(self._device) if self._device is not None else contextlib.nullcontext():
            return jnp.zeros(shape, dtype=dtype)

    def _put_host(self, host: np.ndarray) -> jax.Array:
        return jax.device_put(host, self._device) if self._device is not None else jnp.asarray(host)

    def _place_row(self, row: Dict[str, np.ndarray]):
        return row  # uncommitted host arrays; the _append jit places them

    def _place_block(self, block: Dict[str, np.ndarray]):
        return block  # uncommitted host arrays; the _append_window jit places them

    # ------------------------------------------------------------- write
    def add(self, data: Dict[str, np.ndarray], indices: Optional[Sequence[int]] = None) -> None:
        """Mirror of ``EnvIndependentReplayBuffer.add``: ``data`` is
        (T, n_envs_in, *feat); ``indices`` routes columns to env rings
        (default: all envs in order).  T > 1 goes through the windowed
        append — one jit dispatch for the whole block (training loops that
        dispatch-batch their gradient steps batch their appends the same
        way; see sac.py)."""
        if not self.active:
            return
        first = next(iter(data.values()))
        t_len, n_in = first.shape[:2]
        if indices is None:
            if n_in != self.n_envs:
                raise ValueError(f"data has {n_in} env columns, cache has {self.n_envs}")
            indices = range(self.n_envs)
        idx = np.asarray(list(indices), dtype=np.int64)
        if len(idx) != n_in:
            raise ValueError(f"indices ({len(idx)}) must match data env columns ({n_in})")
        if not self._ensure({k: v[:, :1] for k, v in data.items()}):
            return
        if set(data.keys()) != set(self._bufs.keys()):
            # e.g. a resume that flipped buffer.sample_next_obs changes the
            # stored key set; the host path tolerates it, so fall back
            print(
                "DeviceReplayCache: step keys "
                f"{sorted(data.keys())} != cached keys {sorted(self._bufs.keys())} "
                "— cache disabled, training continues on the host feed path"
            )
            self.active = False
            self._bufs = None
            return
        mask_np = np.zeros(self.n_envs, dtype=bool)
        mask_np[idx] = True
        advance = t_len  # write heads move by the FULL window, even when
        if t_len > self.capacity:  # only the last `capacity` rows survive
            data = {k: v[-self.capacity:] for k, v in data.items()}
            t_len = self.capacity
        if t_len == 1:
            row = {}
            for k, v in data.items():
                full_row = np.zeros((self.n_envs, *v.shape[2:]), dtype=v.dtype)
                full_row[idx] = v[0]
                row[k] = full_row
            row = self._place_row(row)
            self._bufs = _append(
                self._bufs, row, jnp.asarray(self._pos), jnp.asarray(mask_np), n_envs=self.n_envs
            )
            self._seed_tree_window(self._pos, 1, mask_np)
        else:
            # pad to the fixed dispatch length (masked tail) so a short
            # final flush reuses the steady-state trace instead of
            # recompiling _append_window for its one-off length
            if self._window_pad is None or t_len > self._window_pad:
                self._window_pad = t_len
            pad = self._window_pad
            block = {}
            for k, v in data.items():
                full = np.zeros((pad, self.n_envs, *v.shape[2:]), dtype=v.dtype)
                full[:t_len, idx] = v
                block[k] = full
            block = self._place_block(block)
            valid = np.arange(pad) < t_len
            # truncated windows start where sequential adds would have put
            # the first SURVIVING row: pos advanced by the dropped prefix
            start = (self._pos + (advance - t_len)) % self.capacity
            self._bufs = _append_window(
                self._bufs,
                block,
                jnp.asarray(start),
                jnp.asarray(mask_np),
                jnp.asarray(valid),
                n_envs=self.n_envs,
            )
            self._seed_tree_window(start, pad, mask_np, valid=valid)
        self._pos[idx] = (self._pos[idx] + advance) % self.capacity
        self._filled[idx] = np.minimum(self._filled[idx] + advance, self.capacity)

    def load_from(self, rb) -> None:
        """Bulk re-fill from an ``EnvIndependentReplayBuffer`` (resume path):
        one staged host copy + one device_put per key (no per-slab device
        round-trips; the transfer itself is the floor on a slow link).
        Shape mismatches (resumes that changed buffer.size or env count)
        deactivate the cache — the host feed path still trains fine."""
        if not self.active:
            return
        subs = rb.buffer
        if len(subs) != self.n_envs or any(b.buffer_size != self.capacity for b in subs):
            # unreachable from maybe_create_for (which sizes the cache from
            # this rb); direct callers get a hard error
            raise ValueError(
                f"host buffer ({len(subs)} envs x "
                f"{subs[0].buffer_size if subs else 0}) does not match the "
                f"cache ({self.n_envs} x {self.capacity})"
            )
        example = None
        for b in subs:
            if b.buffer:
                example = {k: np.asarray(v[:1]) for k, v in b.buffer.items()}
                break
        if example is None:
            return  # nothing stored yet
        if not self._admit(example):
            return
        bufs = {}
        for k, v0 in example.items():
            parts = []
            for b in subs:
                if b.buffer and k in b.buffer:
                    parts.append(np.asarray(b.buffer[k]))
                else:
                    parts.append(np.zeros((self.capacity, 1, *v0.shape[2:]), v0.dtype))
            host = np.ascontiguousarray(
                np.concatenate(parts, axis=1), dtype=_store_dtype(v0.dtype)
            )  # (cap, n_envs, *feat)
            bufs[k] = self._put_host(host)
        self._bufs = bufs
        self._pos = np.asarray([b._pos for b in subs], dtype=np.int32)
        self._filled = np.asarray(
            [b.buffer_size if b.full else b._pos for b in subs], dtype=np.int32
        )
        self._reseed_tree_filled()

    # ------------------------------------------------------------- read
    def can_sample(self, seq_len: int) -> bool:
        return self.active and self._bufs is not None and bool(np.all(self._filled >= seq_len))

    def sample(self, n_samples: int, batch_size: int, seq_len: int, key) -> List[Dict[str, jax.Array]]:
        """Draw ``n_samples`` independent (seq_len, batch, *feat) batches as
        a list of device dicts (one per gradient step), mirroring the host
        path's ``rb.sample(...)`` + per-sample feed."""
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(
                f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0"
            )
        if not self.can_sample(seq_len):
            raise ValueError(
                f"Cannot sample a sequence of length {seq_len}. "
                f"Data added so far: {int(self._filled.min())}"
            )
        starts, envs = _sample_draw(
            jnp.asarray(key),
            jnp.asarray(self._pos),
            jnp.asarray(self._filled),
            n_samples=int(n_samples),
            batch_size=int(batch_size),
            seq_len=int(seq_len),
            cap=self.capacity,
            n_envs=self.n_envs,
        )
        return [_sample(self._bufs, s, e, seq_len=int(seq_len)) for s, e in zip(starts, envs)]

    def sample_transitions(
        self,
        n_samples: int,
        batch_size: int,
        key,
        sample_next_obs: bool = False,
        obs_keys: Sequence[str] = (),
    ) -> Dict[str, jax.Array]:
        """Flat-transition draw mirroring ``ReplayBuffer.sample`` — returns
        one device dict shaped (n_samples, batch, *feat) (+ ``next_<k>``
        for ``obs_keys`` when ``sample_next_obs``)."""
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(
                f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0"
            )
        need = 2 if sample_next_obs else 1
        if not (self.active and self._bufs is not None and int(self._filled.min()) >= need):
            raise ValueError("Not enough data in the device cache, add first")
        return _sample_transitions(
            self._bufs,
            jnp.asarray(key),
            jnp.asarray(self._pos),
            jnp.asarray(self._filled),
            n_samples=int(n_samples),
            batch_size=int(batch_size),
            cap=self.capacity,
            n_envs=self.n_envs,
            next_keys=tuple(obs_keys) if sample_next_obs else (),
        )

    def can_sample_transitions(self, sample_next_obs: bool = False) -> bool:
        need = 2 if sample_next_obs else 1
        return self.active and self._bufs is not None and bool(np.all(self._filled >= need))

    # ------------------------------------------------- prioritized replay
    def _reseed_tree_filled(self) -> None:
        """Resume fallback: every stored cell enters at the initial
        priority (uniform-at-start) — used when no saved tree state is
        available; ``load_priority_state`` overwrites it when one is."""
        if not self.prioritized or self._bufs is None:
            return
        self._ensure_tree()
        base = np.where(self._filled >= self.capacity, self._pos, 0)  # (n_envs,)
        offs = (np.arange(self.capacity)[:, None] - base[None, :]) % self.capacity
        stored = offs < self._filled[None, :]  # (cap, n_envs) cell-filled mask
        vals = stored.astype(np.float32).reshape(-1)
        n = self.capacity * self.n_envs
        self._tree.set_priorities(np.arange(n), vals)

    def update_priorities(self, idx, td_abs) -> None:
        """TD-error feedback hook for the train loops: ``idx`` is the
        leaf-index array returned by the prioritized samplers (any shape),
        ``td_abs`` the matching |δ|.  Stays on device end to end."""
        if self._tree is None:
            return
        idx = jnp.asarray(idx).reshape(-1)
        self._tree.update(idx, jnp.asarray(td_abs).reshape(-1))

    def sample_transitions_per(
        self,
        n_samples: int,
        batch_size: int,
        key,
        beta: float,
        sample_next_obs: bool = False,
        obs_keys: Sequence[str] = (),
    ):
        """Prioritized flat-transition draw: like :meth:`sample_transitions`
        plus an ``is_weights`` key (n_samples, batch, 1); returns
        ``(batch_dict, idx)`` where ``idx`` feeds
        :meth:`update_priorities` after the train step."""
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(
                f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0"
            )
        need = 2 if sample_next_obs else 1
        if not (self.active and self._bufs is not None and int(self._filled.min()) >= need):
            raise ValueError("Not enough data in the device cache, add first")
        if self._tree is None:
            raise RuntimeError("prioritized sampling requested on a cache built without prioritized=True")
        return _sample_transitions_prioritized(
            self._bufs,
            self._tree.tree,
            jnp.asarray(key),
            jnp.asarray(self._pos),
            jnp.asarray(self._filled),
            jnp.asarray(float(beta), jnp.float32),
            n_samples=int(n_samples),
            batch_size=int(batch_size),
            cap=self.capacity,
            n_envs=self.n_envs,
            next_keys=tuple(obs_keys) if sample_next_obs else (),
            depth=self._tree.depth,
        )

    def sample_per(
        self, n_samples: int, batch_size: int, seq_len: int, key, beta: float
    ) -> List[Dict[str, jax.Array]]:
        """Prioritized sequence-start draw (Dreamer family): same output
        layout as :meth:`sample`; start cells drawn proportional to
        priority.  With ``per_decay`` set, sampled starts are decayed
        afterwards — recency-biased replay without a TD signal (fresh
        windows keep max priority until visited)."""
        if not self.can_sample(seq_len):
            raise ValueError(
                f"Cannot sample a sequence of length {seq_len}. "
                f"Data added so far: {int(self._filled.min())}"
            )
        if self._tree is None:
            raise RuntimeError("prioritized sampling requested on a cache built without prioritized=True")
        starts, envs, leaves = _sample_draw_prioritized(
            self._tree.tree,
            jnp.asarray(key),
            jnp.asarray(self._pos),
            jnp.asarray(self._filled),
            jnp.asarray(float(beta), jnp.float32),
            n_samples=int(n_samples),
            batch_size=int(batch_size),
            seq_len=int(seq_len),
            cap=self.capacity,
            n_envs=self.n_envs,
            depth=self._tree.depth,
        )
        if self.per_decay is not None:
            self._tree.scale(leaves, self.per_decay)
        return [_sample(self._bufs, s, e, seq_len=int(seq_len)) for s, e in zip(starts, envs)]

    def priority_state(self) -> Optional[Dict[str, Any]]:
        """Checkpoint payload for the tree (None when not prioritized) —
        rides the CheckpointManager snapshot next to the host buffer."""
        return self._tree.state_dict() if self._tree is not None else None

    def load_priority_state(self, state: Optional[Dict[str, Any]]) -> None:
        if not self.prioritized or not self.active or self._bufs is None:
            return
        self._ensure_tree()
        if state is None:
            self._reseed_tree_filled()
        else:
            self._tree.load_state_dict(state)

    def load_from_replay(self, rb) -> None:
        """Refill from a plain (flat-transition) ``ReplayBuffer``."""
        if not self.active:
            return
        if rb.buffer_size != self.capacity or rb.n_envs != self.n_envs:
            # unreachable from maybe_create_for_transitions (which sizes the
            # cache from this rb); direct callers get a hard error
            raise ValueError(
                f"host buffer ({rb.n_envs} envs x {rb.buffer_size}) does not "
                f"match the cache ({self.n_envs} x {self.capacity})"
            )
        if not rb.buffer:
            return  # nothing stored yet
        example = {k: np.asarray(v[:1]) for k, v in rb.buffer.items()}
        if not self._admit(example):
            return
        self._bufs = {
            k: (
                jax.device_put(
                    np.ascontiguousarray(np.asarray(v), dtype=_store_dtype(v.dtype)),
                    self._device,
                )
                if self._device is not None
                else jnp.asarray(np.ascontiguousarray(np.asarray(v), dtype=_store_dtype(v.dtype)))
            )
            for k, v in rb.buffer.items()
        }
        pos = int(rb._pos)
        filled = self.capacity if rb.full else pos
        self._pos = np.full(self.n_envs, pos, dtype=np.int32)
        self._filled = np.full(self.n_envs, filled, dtype=np.int32)
        self._reseed_tree_filled()

    # ------------------------------------------------------------ factory
    @classmethod
    def maybe_create(cls, cfg, runtime, capacity: int, n_envs: int) -> Optional["DeviceReplayCache"]:
        """Create when gating allows (see module docstring), else None."""
        mode = device_cache_setting(cfg)
        prioritized = bool(cfg.buffer.get("prioritized", False))
        if mode == "off":
            if prioritized:
                # the sum-tree lives with the cache — disabling the cache
                # while asking for PER is a config contradiction, not a
                # silent downgrade to uniform sampling
                raise ValueError(
                    "buffer.prioritized=True requires the device sampler, but "
                    "buffer.device_cache=False disables it; drop one of the two "
                    "(device_cache=auto enables the cache wherever PER needs it)"
                )
            return None
        if runtime.device_count != 1 or jax.process_count() != 1:
            # multi-device: both buffer families route to the env-sharded
            # variant via _maybe_create_sharded (prioritized included)
            return None
        if mode == "auto" and runtime.device.platform == "cpu" and not prioritized:
            return None  # host-platform run: device_put is free, no win
        budget_gb = float(cfg.buffer.get("device_cache_budget_gb", 6.0))
        cache = cls(
            capacity,
            n_envs,
            device=runtime.device,
            budget_bytes=int(budget_gb * 1e9) if mode == "auto" else None,
            prioritized=prioritized,
            per_alpha=float(cfg.buffer.get("per_alpha", 0.6)),
            per_eps=float(cfg.buffer.get("per_eps", 1e-6)),
            per_decay=cfg.buffer.get("per_decay_on_sample", None),
        )
        print(
            f"DeviceReplayCache: HBM-resident replay window enabled "
            f"(capacity {capacity} x {n_envs} envs, mode={mode}"
            + (", prioritized" if prioritized else "")
            + ")"
        )
        return cache


class ShardedDeviceReplayCache(DeviceReplayCache):
    """Env-sharded cache for single-process multi-device meshes.

    Each device holds the rings of ``n_envs / n_devices`` environments
    (buffers sharded ``P(None, BATCH_AXES)`` over the env axis) and
    uniform sampling draws each device's ``batch / n_devices`` rows from
    its OWN envs inside a ``shard_map`` — appends and gathers stay
    device-local, and the sampled batch comes out already sharded on the
    batch axis exactly as ``runtime.batch_sharding(axis=1)`` lays it out
    for the train step.

    Uniform sampling semantics vs the host path: env choice becomes
    STRATIFIED (exactly batch/n_devices rows from each device's env
    subset) instead of globally uniform — identical marginals, slightly
    lower variance.  Start-window validity per env is unchanged.

    **Prioritized** sampling is fully supported via per-shard sub-trees
    (:class:`~sheeprl_tpu.replay.priority_tree.ShardedPriorityTree`):
    each draw costs ONE psum'd total-mass reduction placing every shard's
    mass interval in the global CDF, each shard descends its own sub-tree
    for the draws it owns, and the batch is assembled with a masked psum
    — so the sampled marginals are IDENTICAL to a single global sum-tree
    (pinned by tests/test_parallel/test_sharding.py).  The assembled PER
    batch is replicated (the psum is the price of exact global
    proportionality); the train step's batch constraint re-slices it.

    Storage and ring/append/refill logic are inherited — this class
    overrides only the array-placement hooks, the tree flavor, and the
    samplers."""

    def __init__(
        self,
        capacity: int,
        n_envs: int,
        runtime,
        budget_bytes: Optional[int] = None,
        prioritized: bool = False,
        per_alpha: float = 0.6,
        per_eps: float = 1e-6,
        per_decay: Optional[float] = None,
    ):
        n_dev = runtime.device_count
        if n_envs % n_dev:
            raise ValueError(f"n_envs ({n_envs}) must divide over {n_dev} devices")
        super().__init__(
            capacity,
            n_envs,
            device=None,
            budget_bytes=budget_bytes,
            prioritized=prioritized,
            per_alpha=per_alpha,
            per_eps=per_eps,
            per_decay=per_decay,
        )
        self._runtime = runtime
        self._n_dev = n_dev
        from jax.sharding import NamedSharding, PartitionSpec as P

        from sheeprl_tpu.parallel.sharding import BATCH_AXES

        self._axes = BATCH_AXES
        self._fsdp_size = int(runtime.mesh.shape[BATCH_AXES[1]])
        self._env_sharding = NamedSharding(runtime.mesh, P(None, BATCH_AXES))
        self._row_sharding = NamedSharding(runtime.mesh, P(BATCH_AXES))
        self._sharded_sample_fns = {}

    def _ensure_tree(self) -> None:
        if self.prioritized and self._tree is None:
            from sheeprl_tpu.replay.priority_tree import ShardedPriorityTree

            self._tree = ShardedPriorityTree(
                self.capacity,
                self.n_envs,
                self._n_dev,
                self._runtime.mesh,
                alpha=self.per_alpha,
                eps=self.per_eps,
            )

    def _flat_rank(self):
        """Flattened shard index inside a shard_map body (the env slice
        this device owns — matches the P(None, BATCH_AXES) split order)."""
        return (
            jax.lax.axis_index(self._axes[0]) * self._fsdp_size
            + jax.lax.axis_index(self._axes[1])
        )

    # ---- placement hooks: same logic as the base, sharded arrays
    def _per_device_envs(self) -> int:
        # each device's shard_map gather addresses only its env slice
        return self.n_envs // self._n_dev

    def _zeros(self, shape, dtype):
        # device-native zeros: the rings are donated by _append, and a
        # donated buffer must never zero-copy alias a host numpy temp
        return jax.device_put(jnp.zeros(shape, dtype), self._env_sharding)

    def _put_host(self, host: np.ndarray) -> jax.Array:
        return jax.device_put(host, self._env_sharding)

    def _place_row(self, row):
        return {k: jax.device_put(v, self._row_sharding) for k, v in row.items()}

    def _place_block(self, block):
        # (T, n_envs, *feat): env axis is dim 1, same layout as the rings
        return {k: jax.device_put(v, self._env_sharding) for k, v in block.items()}

    # ---- per-device stratified sampler
    def sample(self, n_samples: int, batch_size: int, seq_len: int, key) -> List[Dict[str, jax.Array]]:
        if batch_size % self._n_dev:
            raise ValueError(
                f"batch_size ({batch_size}) must divide over {self._n_dev} devices"
            )
        if not self.can_sample(seq_len):
            raise ValueError(
                f"Cannot sample a sequence of length {seq_len}. "
                f"Data added so far: {int(self._filled.min())}"
            )
        n_samples, batch_size, seq_len = int(n_samples), int(batch_size), int(seq_len)
        draw = self._sharded_fn(
            ("draw", n_samples, batch_size, seq_len), self._build_sharded_draw, n_samples, batch_size, seq_len
        )
        read = self._sharded_fn(("read", seq_len, tuple(sorted(self._bufs))), self._build_sharded_read, seq_len)
        starts, envs = draw(jnp.asarray(key), jnp.asarray(self._pos), jnp.asarray(self._filled))
        return [read(self._bufs, s, e) for s, e in zip(starts, envs)]

    def _sharded_fn(self, geom, build, *args):
        """The jitted shard_map program of one geometry, built once."""
        fn = self._sharded_sample_fns.get(geom)
        if fn is None:
            fn = self._sharded_sample_fns[geom] = build(*args)
        return fn

    def _build_sharded_draw(self, n_samples, batch_size, seq_len):
        """Each device's index draw over its own envs, cut per gradient step."""
        from jax.sharding import PartitionSpec as P

        axes = self._axes
        cap, n_dev = self.capacity, self._n_dev
        n_local = self.n_envs // n_dev

        def body_draw(key, pos_l, filled_l):
            # per-device independent stream; each device samples its own envs
            k = jax.random.fold_in(key, self._flat_rank())
            starts, envs = _draw_windows(
                k, pos_l, filled_l,
                flat=n_samples * (batch_size // n_dev), seq_len=seq_len, cap=cap, n_envs=n_local,
            )
            return _rows(starts, n_samples), _rows(envs, n_samples)

        rows = (P(axes),) * n_samples
        sharded = shard_map(
            body_draw, mesh=self._runtime.mesh,
            in_specs=(P(), P(axes), P(axes)),
            out_specs=(rows, rows),
            check_vma=False,
        )
        return jax.jit(sharded)

    def _build_sharded_read(self, seq_len):
        """One gradient step's windows, each device reading its own draws
        out of its own rings: the batch axis comes out sharded."""
        from jax.sharding import PartitionSpec as P

        axes = self._axes

        def body(bufs_l, starts_l, envs_l):
            return _read_windows(bufs_l, starts_l, envs_l, seq_len=seq_len)

        sharded = shard_map(
            body, mesh=self._runtime.mesh,
            in_specs=({k: P(None, axes) for k in self._bufs}, P(axes), P(axes)),
            out_specs={k: P(None, axes) for k in self._bufs},
            check_vma=False,
        )
        return jax.jit(sharded)

    # --------------------------------------------- sharded flat transitions
    def sample_transitions(
        self,
        n_samples: int,
        batch_size: int,
        key,
        sample_next_obs: bool = False,
        obs_keys: Sequence[str] = (),
    ) -> Dict[str, jax.Array]:
        """Stratified uniform flat-transition draw: each device gathers
        ``batch / n_devices`` rows from its own env columns (same
        marginals as the global uniform draw; zero collectives)."""
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(
                f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0"
            )
        if batch_size % self._n_dev:
            raise ValueError(f"batch_size ({batch_size}) must divide over {self._n_dev} devices")
        need = 2 if sample_next_obs else 1
        if not (self.active and self._bufs is not None and int(self._filled.min()) >= need):
            raise ValueError("Not enough data in the device cache, add first")
        nk = tuple(obs_keys) if sample_next_obs else ()
        geom = ("transitions", int(n_samples), int(batch_size), nk, tuple(sorted(self._bufs)))
        fn = self._sharded_sample_fns.get(geom)
        if fn is None:
            fn = self._build_sharded_sample_transitions(int(n_samples), int(batch_size), nk)
            self._sharded_sample_fns[geom] = fn
        return fn(self._bufs, jnp.asarray(key), jnp.asarray(self._pos), jnp.asarray(self._filled))

    def _build_sharded_sample_transitions(self, n_samples, batch_size, next_keys):
        from jax.sharding import PartitionSpec as P

        mesh = self._runtime.mesh
        axes = self._axes
        cap, n_dev = self.capacity, self._n_dev
        n_local = self.n_envs // n_dev
        b_local = batch_size // n_dev

        def body(bufs_l, key, pos_l, filled_l):
            k = jax.random.fold_in(key, self._flat_rank())
            flat = n_samples * b_local
            k_env, k_row = jax.random.split(k)
            envs = jax.random.randint(k_env, (flat,), 0, n_local)
            base, count = _transition_window(pos_l, filled_l, cap=cap, next_keys=next_keys)
            u = jax.random.uniform(k_row, (flat,))
            offs = jnp.minimum((u * count).astype(jnp.int32), count - 1)
            rows = (base + offs) % cap
            return _gather_transitions(
                bufs_l, rows, envs,
                n_samples=n_samples, batch_size=b_local, cap=cap, next_keys=next_keys,
            )

        buf_specs = {k: P(None, axes) for k in self._bufs}
        out_keys = list(self._bufs) + [f"next_{k}" for k in next_keys]
        out_specs = {k: P(None, axes) for k in out_keys}
        sharded = shard_map(
            body, mesh=mesh,
            in_specs=(buf_specs, P(), P(axes), P(axes)),
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(sharded)

    # ------------------------------------------------- sharded prioritized
    def sample_transitions_per(
        self,
        n_samples: int,
        batch_size: int,
        key,
        beta: float,
        sample_next_obs: bool = False,
        obs_keys: Sequence[str] = (),
    ):
        if batch_size <= 0 or n_samples <= 0:
            raise ValueError(
                f"'batch_size' ({batch_size}) and 'n_samples' ({n_samples}) must be both greater than 0"
            )
        need = 2 if sample_next_obs else 1
        if not (self.active and self._bufs is not None and int(self._filled.min()) >= need):
            raise ValueError("Not enough data in the device cache, add first")
        if self._tree is None:
            raise RuntimeError("prioritized sampling requested on a cache built without prioritized=True")
        nk = tuple(obs_keys) if sample_next_obs else ()
        geom = ("per_transitions", int(n_samples), int(batch_size), nk, tuple(sorted(self._bufs)))
        fn = self._sharded_sample_fns.get(geom)
        if fn is None:
            fn = self._build_sharded_per(int(n_samples), int(batch_size), None, nk)
            self._sharded_sample_fns[geom] = fn
        out, leaves = fn(
            self._bufs,
            self._tree.trees,
            jnp.asarray(key),
            jnp.asarray(self._pos),
            jnp.asarray(self._filled),
            jnp.asarray(float(beta), jnp.float32),
        )
        return out, leaves

    def sample_per(
        self, n_samples: int, batch_size: int, seq_len: int, key, beta: float
    ) -> List[Dict[str, jax.Array]]:
        if not self.can_sample(seq_len):
            raise ValueError(
                f"Cannot sample a sequence of length {seq_len}. "
                f"Data added so far: {int(self._filled.min())}"
            )
        if self._tree is None:
            raise RuntimeError("prioritized sampling requested on a cache built without prioritized=True")
        n_samples, batch_size, seq_len = int(n_samples), int(batch_size), int(seq_len)
        draw = self._sharded_fn(
            ("per_windows", n_samples, batch_size, seq_len),
            self._build_sharded_per, n_samples, batch_size, seq_len, (),
        )
        read = self._sharded_fn(
            ("per_read", seq_len, tuple(sorted(self._bufs))), self._build_sharded_per_read, seq_len
        )
        (rows, envs, own), leaves = draw(
            self._bufs,
            self._tree.trees,
            jnp.asarray(key),
            jnp.asarray(self._pos),
            jnp.asarray(self._filled),
            jnp.asarray(0.0, jnp.float32),
        )
        if self.per_decay is not None:
            self._tree.scale(leaves, self.per_decay)
        return [read(self._bufs, r, e, o) for r, e, o in zip(rows, envs, own)]

    def _build_sharded_per_read(self, seq_len):
        """One gradient step's prioritized windows: every shard reads the
        draws it owns out of its own rings and the masked psum assembles
        the (replicated) batch."""
        from jax.sharding import PartitionSpec as P

        axes = self._axes

        def body(bufs_l, rows_l, envs_l, own_l):
            out = {}
            for k, g in _read_windows(bufs_l, rows_l, envs_l, seq_len=seq_len).items():
                m = own_l.reshape((1, -1) + (1,) * (g.ndim - 2))  # g is (L, B, *feat)
                out[k] = jax.lax.psum(jnp.where(m, g, jnp.zeros((), g.dtype)), axes)
            return out

        sharded = shard_map(
            body, mesh=self._runtime.mesh,
            in_specs=({k: P(None, axes) for k in self._bufs}, P(axes), P(axes), P(axes)),
            out_specs={k: P() for k in self._bufs},
            check_vma=False,
        )
        return jax.jit(sharded)

    def _build_sharded_per(self, n_samples, batch_size, seq_len, next_keys):
        """One builder for both prioritized shapes: ``seq_len=None`` gives
        the flat-transition sampler (+ IS weights), an int gives the
        sequence-START sampler (Dreamer family; no IS reweighting).

        The body runs per shard: zero this shard's invalid cells in a
        functional sub-tree copy, draw globally via
        :func:`~sheeprl_tpu.replay.priority_tree.shard_proportional_draw`
        (ONE psum'd total-mass reduction), gather rows for the draws this
        shard owns, and masked-psum the batch together — exact global
        proportional marginals, replicated output.  The sequence sampler
        stops after the draw: :meth:`_build_sharded_per_read` reads and
        assembles each gradient step's windows."""
        from jax.sharding import PartitionSpec as P

        from sheeprl_tpu.replay.priority_tree import (
            _tree_zeroed_local,
            shard_proportional_draw,
        )

        mesh = self._runtime.mesh
        axes = self._axes
        cap, n_envs, n_dev = self.capacity, self.n_envs, self._n_dev
        n_local = n_envs // n_dev
        depth = self._tree.depth
        flat = n_samples * batch_size
        windows = seq_len is not None

        def body(bufs_l, trees_l, key, pos_l, filled_l, beta):
            r = self._flat_rank()
            t = trees_l[0]
            # shard-local sampling exclusions (invalid window starts /
            # stale-next-obs head rows): pre-zeroed in a functional
            # sub-tree copy
            excl = None
            if windows and seq_len > 1:  # jaxlint: disable=retrace-branch — static window length
                offs = jnp.arange(1, seq_len)  # (L-1,)
                inv_rows = (pos_l[None, :] - offs[:, None]) % cap  # (L-1, n_local)
                excl = (inv_rows * n_local + jnp.arange(n_local)[None, :]).reshape(-1)
            if not windows and next_keys:  # jaxlint: disable=retrace-branch — static obs-key tuple
                head_rows = (pos_l - 1) % cap  # per-env newest row: successor is stale
                excl = head_rows * n_local + jnp.arange(n_local)
            if excl is not None:
                t = _tree_zeroed_local(t, excl, depth)
            leaf, mass, own, total = shard_proportional_draw(
                t, key, r, n_dev, axes, n=flat, depth=depth
            )
            rows = leaf // n_local
            env_l = leaf % n_local
            cell_global = rows * n_envs + (r * n_local + env_l)
            leaves_out = jax.lax.psum(jnp.where(own, cell_global, 0), axes)

            out = {}
            if windows:
                # the draw alone, cut per gradient step: each shard's rows,
                # envs and ownership go to _build_sharded_per_read
                out = tuple(_rows(v, n_samples) for v in (rows, env_l, own))
            else:
                gathered = _gather_transitions(
                    bufs_l, rows, env_l,
                    n_samples=n_samples, batch_size=batch_size, cap=cap, next_keys=next_keys,
                )
                own_b = own.reshape(n_samples, batch_size)
                for k, g in gathered.items():
                    m = own_b.reshape(own_b.shape + (1,) * (g.ndim - 2))
                    out[k] = jax.lax.psum(jnp.where(m, g, jnp.zeros((), g.dtype)), axes)
                # IS weights from the psum-assembled per-draw masses (all
                # shards agree, so the batch-max normalization is global)
                mass_global = jax.lax.psum(jnp.where(own, mass, 0.0), axes)
                live_local = jnp.sum(filled_l) - (n_local if next_keys else 0)
                n_live = jax.lax.psum(live_local.astype(jnp.float32), axes)
                probs = jnp.maximum(mass_global, jnp.finfo(jnp.float32).tiny) / jnp.maximum(
                    total, jnp.finfo(jnp.float32).tiny
                )
                w = (jnp.maximum(n_live, 1.0) * probs) ** (-beta)
                w = w / jnp.max(w)
                out["is_weights"] = w.reshape(n_samples, batch_size, 1)
            return out, leaves_out.reshape(n_samples, batch_size)

        buf_specs = {k: P(None, axes) for k in self._bufs}
        out_keys = list(self._bufs) + [f"next_{k}" for k in next_keys]
        if not windows:
            out_keys.append("is_weights")
        out_specs = (((P(axes),) * n_samples,) * 3 if windows else {k: P() for k in out_keys}, P())
        sharded = shard_map(
            body, mesh=mesh,
            in_specs=(buf_specs, P(axes, None), P(), P(axes), P(axes), P()),
            out_specs=out_specs,
            check_vma=False,
        )
        return jax.jit(sharded)
