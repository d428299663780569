"""Training health sentinel — on-device anomaly detection, bad-update
skipping, and automatic rollback-to-last-good (ISSUE 7).

PR 2 made the stack survive *process* failures and PR 6 *topology*
failures; this module makes it survive the training math going bad.  A
NaN loss, an exploding gradient, or a poisoned replay batch otherwise
silently corrupts params, gets dutifully checkpointed, broadcast to every
player, and rides ``resume_from=auto`` forever.  Three layers:

1. **On-device detection inside the jitted update** — every algo's
   update builder routes through :func:`guard_update`, which appends a
   cheap fused monitor to the jitted program: a finite-check plus an
   EMA-z-score test over the update's loss/grad-norm metrics and the
   global param-update norm.  One jit dispatch, no host sync on the hot
   path: the verdict lives in a tiny :class:`SentinelState` pytree that
   rides the dispatch chain like the params do.
2. **Bad-update skipping** — an anomalous update is discarded *before*
   it touches params/opt-state (``optax.apply_if_finite`` generalized to
   the z-score verdict): every state output of the update (params, opt
   states, moments, ...) is predicated on the verdict, so a skipped
   update leaves training state bit-identical to the pre-update state.
3. **Automatic rollback** — ``sentinel.skip_budget`` consecutive skips
   mean skipping is not enough (the optimizer/ratio state may be in a
   diverging basin, or the fault is persistent): :meth:`TrainHealth.tick`
   restores the last checkpoint tagged **good** (a checkpoint is only
   promoted good after ``sentinel.good_after`` healthy updates; pending
   ones are quarantined on a trip and ``resume_from=auto`` never selects
   them), re-seeds the host PRNG key stream, and — in decoupled runs —
   the trainer's next params broadcast re-adopts every player through the
   existing :class:`~sheeprl_tpu.parallel.transport.ParamsFollower` path.

Provably free: with ``sentinel.enabled=false`` (default) the builders
return the exact pre-sentinel jitted step — not one traced op changes.
With the sentinel on and no anomaly, the verdict select passes the
computed update through unchanged, so agent params stay bit-exact with a
sentinel-off run and the post-warmup compile counter stays flat (the
monitor is part of the one traced program).

See ``howto/resilience.md`` ("Training health & rollback") for the
operational model and the ``health`` telemetry key schema.
"""

from __future__ import annotations

import json
import os
import warnings
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple


class TrainingDivergedError(RuntimeError):
    """The sentinel's consecutive-skip budget tripped and no good
    checkpoint exists to roll back to: training cannot make progress.
    Raised instead of silently continuing on (frozen) params so an
    unattended run fails loudly with a diagnosable message."""


# --------------------------------------------------------------------- config
_DEFAULTS: Dict[str, Any] = {
    "enabled": False,
    # z-score threshold: a monitored stat more than z_max EMA standard
    # deviations from its EMA mean flags the update (after warmup)
    "z_max": 6.0,
    # EMA smoothing for the per-stat mean/variance baseline
    "ema_alpha": 0.02,
    # updates absorbed into the baseline before z-scores can flag (the
    # finite-check is armed from update 1)
    "warmup": 20,
    # consecutive skipped updates before rollback-to-last-good triggers
    "skip_budget": 3,
    # healthy updates after a save before a checkpoint is tagged "good"
    "good_after": 10,
    # host-side verdict poll cadence (in update dispatches); >1 amortizes
    # the tiny device fetch on high-latency links at the cost of detecting
    # a budget trip up to check_every-1 dispatches late
    "check_every": 1,
}


def sentinel_setting(cfg) -> Dict[str, Any]:
    """Resolve ``cfg.algo.sentinel`` to a plain knob dict (defaults when
    the node is absent, e.g. external-algorithm configs)."""
    node: Any = {}
    try:
        node = cfg.algo.get("sentinel", None) or {}
    except AttributeError:
        pass
    out = dict(_DEFAULTS)
    for k in out:
        try:
            v = node.get(k, None)
        except AttributeError:
            v = None
        if v is not None:
            out[k] = v
    out["enabled"] = str(out["enabled"]).lower() in ("1", "true", "on", "yes")
    for k in ("z_max", "ema_alpha"):
        out[k] = float(out[k])
    for k in ("warmup", "skip_budget", "good_after", "check_every"):
        out[k] = max(1, int(out[k]))
    return out


# ---------------------------------------------------------------- device side
class SentinelState(NamedTuple):
    """Device-resident monitor state (a tiny pytree riding the update
    dispatch chain; ~(2K+6) scalars for K monitored stats)."""

    mean: Any  # (K,) f32 EMA mean of each monitored stat
    var: Any  # (K,) f32 EMA variance
    count: Any  # () i32  healthy updates absorbed into the baseline
    consec_skips: Any  # () i32  current run of skipped updates
    total_skips: Any  # () i32  skips since init/reset
    last_ok: Any  # () bool verdict of the latest update
    last_z: Any  # (K,) f32 z-scores of the latest update
    tripped: Any  # () bool consec_skips >= skip_budget


def init_sentinel_state(n_stats: int, count0: int = 0) -> SentinelState:
    """``count0 < 0`` extends the effective warmup (used after a rollback:
    the restored weights meet the CURRENT data distribution, so the
    baseline needs longer to settle than at run start — re-arming too
    early false-flags the recovery updates and loops the rollback)."""
    import jax.numpy as jnp

    k = int(n_stats)
    return SentinelState(
        mean=jnp.zeros((k,), jnp.float32),
        var=jnp.zeros((k,), jnp.float32),
        count=jnp.full((), int(count0), jnp.int32),
        consec_skips=jnp.zeros((), jnp.int32),
        total_skips=jnp.zeros((), jnp.int32),
        last_ok=jnp.ones((), bool),
        last_z=jnp.zeros((k,), jnp.float32),
        tripped=jnp.zeros((), bool),
    )


def detector_step(
    state: SentinelState,
    stats,
    *,
    z_max: float,
    ema_alpha: float,
    warmup: int,
    skip_budget: int,
) -> Tuple[Any, SentinelState]:
    """One fused verdict: ``(ok, new_state)`` for a (K,) stats vector.

    - non-finite anywhere -> anomalous, from the very first update;
    - past ``warmup`` healthy updates, any stat more than ``z_max`` EMA
      standard deviations ABOVE its EMA mean -> anomalous.  One-sided on
      purpose: divergence is losses/grad-norms EXPLODING upward, while
      early training legitimately moves stats tens of sigma DOWNWARD
      (fast improvement) — a two-sided test false-trips there;
    - healthy stats move the baseline at full EMA weight, finite-but-
      flagged ones at quarter weight (a genuine regime shift normalizes
      instead of flagging forever), non-finite ones never;
    - the first healthy sample seeds the baseline exactly (an EMA from
      zero would make early z-scores meaningless).
    """
    import jax.numpy as jnp

    stats = jnp.asarray(stats, jnp.float32)
    finite = jnp.all(jnp.isfinite(stats))
    # denominator floor: sqrt(var) alone makes a smoothly-DRIFTING stat
    # with near-zero variance (a cleanly decaying loss late in training)
    # trip on tiny deviations; the 1% relative floor means a stat must
    # move by >= z_max% of its own magnitude before it can flag
    denom = jnp.sqrt(jnp.maximum(state.var, 0.0)) + 0.01 * jnp.abs(state.mean) + 1e-6
    z = (stats - state.mean) / denom  # SIGNED: only upward excursions flag
    z = jnp.where(jnp.isfinite(z), z, jnp.inf)
    warmed = state.count >= warmup
    ok = finite & (~warmed | (jnp.max(z) <= z_max))

    safe = jnp.where(jnp.isfinite(stats), stats, state.mean)
    # healthy stats move the baseline at full weight; finite-but-flagged
    # ones at quarter weight — a genuine regime shift (post-rollback
    # catch-up training, a new curriculum stage) then normalizes within
    # ~4/alpha updates instead of flagging forever, while NaN/inf never
    # touch the baseline at all (``safe`` substitutes the mean)
    a = jnp.where(ok, jnp.float32(ema_alpha), jnp.float32(ema_alpha) * 0.25)
    a = jnp.where(finite | ok, a, jnp.float32(0.0))
    first = state.count <= 0
    new_mean = jnp.where(first, safe, (1.0 - a) * state.mean + a * safe)
    delta = safe - state.mean
    new_var = jnp.where(
        first, jnp.zeros_like(state.var), (1.0 - a) * state.var + a * delta * delta
    )

    consec = jnp.where(ok, 0, state.consec_skips + 1).astype(state.consec_skips.dtype)
    new_state = SentinelState(
        mean=new_mean,
        var=new_var,
        count=state.count + ok.astype(state.count.dtype),
        consec_skips=consec,
        total_skips=state.total_skips + (~ok).astype(state.total_skips.dtype),
        last_ok=ok,
        last_z=z,
        tripped=consec >= skip_budget,
    )
    return ok, new_state


def _tree_update_norm(new_params, old_params):
    """Global L2 norm of (new - old) over every float leaf — the param
    update magnitude the z-score monitors (a non-finite update makes it
    non-finite, so it doubles as the fused finite check over params)."""
    import jax
    import jax.numpy as jnp

    def leaf_sq(n, o):
        if not (hasattr(n, "dtype") and jnp.issubdtype(n.dtype, jnp.floating)):
            return jnp.zeros((), jnp.float32)
        d = n.astype(jnp.float32) - o.astype(jnp.float32)
        return jnp.sum(d * d)

    sq = jax.tree_util.tree_map(leaf_sq, new_params, old_params)
    return jnp.sqrt(sum(jax.tree_util.tree_leaves(sq)))


def restore_like(live_tree, saved_tree):
    """Materialize a checkpointed (host numpy) pytree back onto device with
    the structure/dtype/sharding of the live tree it replaces — the one
    generic rollback restore every algo loop shares (rollback happens
    within one run, so no precision/structure migration is needed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def leaf(live, saved):
        if hasattr(live, "dtype"):
            # copy=True: CPU device_put ZERO-COPY aliases aligned host
            # buffers, and the loaded checkpoint tree is garbage-collected
            # right after the restore — an aliasing array would then read
            # freed memory mid-update (the PR-3 use-after-free family)
            arr = jnp.array(np.asarray(saved), dtype=live.dtype, copy=True)
            sharding = getattr(live, "sharding", None)
            return jax.device_put(arr, sharding) if sharding is not None else arr
        return saved

    return jax.tree_util.tree_map(leaf, live_tree, saved_tree)


# ------------------------------------------------------------- fault adapters
def _poison_tree(data, value: float):
    """Scale every float leaf of a batch pytree by ``value`` (NaN for
    ``nan_inject``, a large finite factor for ``loss_spike``) keeping
    dtypes — the injected batch is indistinguishable from a genuinely
    poisoned one by the time the update consumes it."""
    import jax
    import numpy as np

    def leaf(x):
        dt = getattr(x, "dtype", None)
        if dt is None or not np.issubdtype(np.dtype(dt), np.floating):
            return x
        return x * np.asarray(value, dtype=dt)

    return jax.tree_util.tree_map(leaf, data)


class _UpdateFaults:
    """``nan_inject`` / ``loss_spike`` fault sites (resilience/faults.py):
    poison the update's data batch so the produced gradients/params are
    non-finite (or spiked) — the adversary the sentinel trains against.

    ``nan_inject:k:n`` poisons ``n`` CONSECUTIVE dispatches starting at
    the k-th (default 1 — the repeat is how a chaos run trips the skip
    budget, since spec entries are one-shots that cannot fire
    back-to-back); ``loss_spike:k:s`` scales float leaves by ``s``
    (default 1e4) at the k-th dispatch.  Armed-spec check only when
    SHEEPRL_FAULTS is set; free otherwise."""

    def __init__(self) -> None:
        self._left = 0
        self._value = 0.0

    def apply(self, args: tuple, n_state: int) -> tuple:
        from sheeprl_tpu.resilience.faults import get_injector

        inj = get_injector()
        if (not inj.armed and self._left <= 0) or len(args) <= n_state:
            return args
        if self._left <= 0:
            if inj.fire("nan_inject"):
                self._value = float("nan")
                self._left = max(1, int(inj.arg("nan_inject")) or 1)
            elif inj.fire("loss_spike"):
                self._value = float(inj.arg("loss_spike")) or 1e4
                self._left = 1
            else:
                return args
        self._left -= 1
        return args[:n_state] + (_poison_tree(args[n_state], self._value),) + args[n_state + 1 :]


# ------------------------------------------------------------ checkpoint tags
class CheckpointHealthTags:
    """good/pending/quarantined tagging sidecar (``health_tags.json``
    next to the ``ckpt_*.ckpt`` files; atomic tmp+rename writes).

    Lifecycle: a save lands as ``pending``; after ``good_after`` healthy
    updates with no anomaly in between it is promoted ``good``; a
    budget trip quarantines everything still pending (its params may be
    fine, but its optimizer/counters were saved inside the diverging
    window).  ``resume_from=auto`` and rollback never select a
    quarantined checkpoint."""

    FILENAME = "health_tags.json"

    def __init__(self, ckpt_dir: str):
        self.ckpt_dir = str(ckpt_dir)
        self.path = os.path.join(self.ckpt_dir, self.FILENAME)
        self._tags: Dict[str, Dict[str, Any]] = {}
        self._load()

    # ------------------------------------------------------------- persistence
    def _load(self) -> None:
        try:
            with open(self.path) as f:
                self._tags = {str(k): dict(v) for k, v in json.load(f).items()}
        except (OSError, ValueError):
            self._tags = {}

    def _save(self) -> None:
        try:
            os.makedirs(self.ckpt_dir, exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self._tags, f, separators=(",", ":"))
            os.replace(tmp, self.path)
        except OSError:
            pass  # tagging is best-effort; rollback falls back to validation

    # ------------------------------------------------------------- transitions
    def note_save(self, ckpt_path: str, healthy_marker: int) -> None:
        name = os.path.basename(str(ckpt_path))
        # prune BEFORE adding: an async save's file is not on disk yet
        # when its tag lands, and pruning the in-flight entry would leave
        # the newest checkpoint untagged forever
        self._prune()
        self._tags[name] = {"status": "pending", "marker": int(healthy_marker)}
        self._save()

    def note_anomaly(self, healthy_marker: int) -> None:
        """A skipped update restarts every pending checkpoint's
        K-healthy-updates promotion count."""
        changed = False
        for v in self._tags.values():
            if v.get("status") == "pending":
                v["marker"] = int(healthy_marker)
                changed = True
        if changed:
            self._save()

    def promote(self, healthy_marker: int, good_after: int) -> None:
        changed = False
        for v in self._tags.values():
            if v.get("status") == "pending" and healthy_marker - v.get("marker", 0) >= good_after:
                v["status"] = "good"
                changed = True
        if changed:
            self._save()

    def quarantine_pending(self) -> List[str]:
        hit = []
        for name, v in self._tags.items():
            if v.get("status") == "pending":
                v["status"] = "quarantined"
                hit.append(name)
        if hit:
            self._save()
        return hit

    def _prune(self) -> None:
        """Drop tags whose checkpoint file retention already deleted."""
        gone = [n for n in self._tags if not os.path.exists(os.path.join(self.ckpt_dir, n))]
        for n in gone:
            del self._tags[n]

    # ------------------------------------------------------------- queries
    def status(self, ckpt_path: str) -> Optional[str]:
        entry = self._tags.get(os.path.basename(str(ckpt_path)))
        return entry.get("status") if entry else None

    def good_paths(self) -> List[str]:
        """Good-tagged checkpoint paths, newest mtime first."""
        out = []
        for name, v in self._tags.items():
            if v.get("status") == "good":
                p = os.path.join(self.ckpt_dir, name)
                if os.path.exists(p):
                    out.append(p)
        return sorted(out, key=os.path.getmtime, reverse=True)

    def stats(self) -> Dict[str, int]:
        c: Dict[str, int] = {"pending": 0, "good": 0, "quarantined": 0}
        for v in self._tags.values():
            s = v.get("status")
            if s in c:
                c[s] += 1
        return c


def is_quarantined(ckpt_path: str) -> bool:
    """Sidecar lookup used by auto-resume: True when the checkpoint's
    directory tags it quarantined."""
    tags_path = os.path.join(os.path.dirname(str(ckpt_path)), CheckpointHealthTags.FILENAME)
    if not os.path.exists(tags_path):
        return False
    try:
        with open(tags_path) as f:
            tags = json.load(f)
    except (OSError, ValueError):
        return False
    entry = tags.get(os.path.basename(str(ckpt_path)))
    return bool(entry) and entry.get("status") == "quarantined"


def find_last_good(scan_root: str, quarantined_extra: Optional[set] = None) -> Optional[str]:
    """Newest rollback-eligible checkpoint under ``scan_root``: prefers
    ``good``-tagged ones; falls back to the newest untagged/pending file
    that validates AND passes the finite spot-check (a run whose first
    trip lands before any promotion must still have somewhere to go).
    ``quarantined_extra`` lets a caller exclude paths it already rejected
    in-memory (the decoupled trainer does not own the sidecar)."""
    from sheeprl_tpu.resilience.autoresume import list_checkpoints
    from sheeprl_tpu.utils.ckpt_format import (
        CheckpointCorruptError,
        spot_check_finite,
        validate_checkpoint,
    )

    skip = {os.path.abspath(p) for p in (quarantined_extra or ())}
    candidates = [
        p
        for p in list_checkpoints(str(scan_root))
        if os.path.abspath(p) not in skip and not is_quarantined(p)
    ]
    tagged_good = []
    seen_dirs = set()
    for p in candidates:
        d = os.path.dirname(p)
        if d not in seen_dirs:
            seen_dirs.add(d)
            tags = CheckpointHealthTags(d)
            tagged_good.extend(tags.good_paths())
    tagged_good = [p for p in tagged_good if os.path.abspath(p) not in skip]
    ordered = sorted(tagged_good, key=os.path.getmtime, reverse=True) + [
        p for p in candidates if p not in set(tagged_good)
    ]
    for ckpt in ordered:
        try:
            validate_checkpoint(ckpt)
            spot_check_finite(ckpt)
            return ckpt
        except CheckpointCorruptError as e:
            warnings.warn(f"rollback: skipping checkpoint ({e})")
    return None


# -------------------------------------------------------------- host side
class TrainHealth:
    """Host orchestrator of the sentinel: polls the device verdict at the
    ``check_every`` cadence, keeps cumulative counters for telemetry,
    drives checkpoint good/quarantine tagging, and performs the rollback
    when the consecutive-skip budget trips.

    One instance rides every :class:`GuardedUpdate` (a disabled no-op
    when ``sentinel.enabled=false``), so loop wiring is uniform::

        health = train_fn.health
        health.bind(ckpt_mgr=ckpt_mgr)          # or scan_root=... (decoupled)
        ...
        rolled = health.tick()                  # once per update dispatch
        if rolled is not None:
            params = restore_like(params, rolled["agent"])
            ...
    """

    def __init__(self, runtime, scfg: Dict[str, Any]):
        self.enabled = bool(scfg["enabled"])
        self._runtime = runtime
        self.cfg = dict(scfg)
        self.device_state: Optional[SentinelState] = None
        self.stat_keys: Optional[List[str]] = None
        # --- host counters (survive device-state resets on rollback)
        self.dispatches = 0
        self._dispatches_at_tick = 0
        self.healthy_marker = 0
        self.skips = 0
        self._skips_at_reset = 0  # host skips folded in at the last device reset
        self.rollbacks = 0
        self.trips = 0
        self.last_ok = True
        self.last_z: Optional[List[float]] = None
        self.last_rollback: Optional[Dict[str, Any]] = None
        self._since_check = 0
        # --- rollback wiring
        self._ckpt_mgr = None
        self._tags: Optional[CheckpointHealthTags] = None
        self._scan_root: Optional[str] = None
        self._select: Optional[Sequence[str]] = None
        self._rejected: set = set()
        self._on_rollback: List[Callable[[str], None]] = []

    # ------------------------------------------------------------- wiring
    def bind(
        self,
        ckpt_mgr=None,
        scan_root: Optional[str] = None,
        select: Optional[Sequence[str]] = None,
    ) -> "TrainHealth":
        """Attach the rollback source: a :class:`CheckpointManager` (the
        coupled loops — tagging rides its saves) and/or a directory to
        scan (the decoupled trainer, which does not own the checkpoint
        files).  ``select`` restricts the rollback load to the given
        top-level checkpoint keys (params/opt only; buffers stay live)."""
        if not self.enabled:
            return self
        self._select = tuple(select) if select else None
        if ckpt_mgr is not None:
            self._ckpt_mgr = ckpt_mgr
            if ckpt_mgr.log_dir:
                self._tags = CheckpointHealthTags(os.path.join(ckpt_mgr.log_dir, "checkpoint"))
            ckpt_mgr.health = self
        if scan_root is not None:
            self._scan_root = str(scan_root)
        return self

    def on_rollback(self, fn: Callable[[str], None]) -> None:
        """Register a callback invoked with the checkpoint path after a
        rollback restore (decoupled trainers broadcast from it)."""
        self._on_rollback.append(fn)

    # hook called by CheckpointManager.checkpoint_now on every save
    def note_checkpoint(self, path: str) -> None:
        if self.enabled and self._tags is not None:
            self._tags.note_save(path, self.healthy_marker)

    # ------------------------------------------------------------- polling
    def note_dispatch(self) -> None:
        self.dispatches += 1

    def tick(self) -> Optional[Dict[str, Any]]:
        """Poll the verdict; returns the restored checkpoint state dict
        when a rollback happened this tick (the loop re-adopts it), else
        None.  Called once per update dispatch by every wired loop."""
        if not self.enabled or self.device_state is None:
            return None
        self._since_check += 1
        if self._since_check < self.cfg["check_every"]:
            return None
        self._since_check = 0
        import jax

        st = self.device_state
        ok, consec, total, tripped, z = jax.device_get(
            (st.last_ok, st.consec_skips, st.total_skips, st.tripped, st.last_z)
        )
        self.last_ok = bool(ok)
        self.last_z = [round(float(v), 3) for v in z]
        # device total_skips counts since the last reset; the host keeps
        # the cumulative figure across rollback resets
        delta_skips = (self._skips_at_reset + int(total)) - self.skips
        d_dispatch = self.dispatches - self._dispatches_at_tick
        self._dispatches_at_tick = self.dispatches
        d_healthy = max(0, d_dispatch - max(0, delta_skips))
        self.healthy_marker += d_healthy
        if delta_skips > 0:
            self.skips += delta_skips
            if self._tags is not None:
                self._tags.note_anomaly(self.healthy_marker)
            warnings.warn(
                f"sentinel: skipped {delta_skips} anomalous update(s) "
                f"(consecutive={int(consec)}, z={self.last_z})"
            )
            from sheeprl_tpu.obs import flight

            flight.fleet_event(
                "sentinel_skip", skipped=int(delta_skips), consecutive=int(consec)
            )
        elif self._tags is not None:
            self._tags.promote(self.healthy_marker, self.cfg["good_after"])
        if bool(tripped):
            return self._rollback(int(consec))
        return None

    # ------------------------------------------------------------- rollback
    def _rollback(self, consec: int) -> Dict[str, Any]:
        from sheeprl_tpu.utils.callback import load_checkpoint

        self.trips += 1
        if self._tags is not None:
            quarantined = self._tags.quarantine_pending()
        else:
            quarantined = []
        scan_root = self._scan_root or (
            os.path.join(self._ckpt_mgr.log_dir, "checkpoint") if self._ckpt_mgr else None
        )
        target = find_last_good(scan_root, quarantined_extra=self._rejected) if scan_root else None
        if target is None and scan_root:
            # last resort: a trip before any promotion quarantined every
            # candidate — a quarantined-but-finite checkpoint (its params
            # were never touched by a SKIPPED update) beats killing the
            # run; it is re-tagged pending so auto-resume can use it too
            target = self._fallback_any_finite(scan_root)
        if target is None:
            raise TrainingDivergedError(
                f"sentinel skip budget tripped ({consec} consecutive anomalous updates) "
                f"and no usable checkpoint exists under {scan_root!r} to roll back to; "
                "last z-scores: " + str(self.last_z)
            )
        state = load_checkpoint(target, select=self._select)
        # fresh detector baseline for the restored weights; cumulative
        # counters live on the host so telemetry keeps the history.  The
        # restored (older) policy meets the CURRENT env/replay data, so the
        # post-rollback warmup is doubled — re-arming on a barely-seeded
        # baseline false-flags the recovery and loops the rollback
        self._skips_at_reset = self.skips
        self.device_state = init_sentinel_state(
            len(self.stat_keys or []),
            # progressive re-arm backoff: each successive rollback doubles
            # the extended warmup again, so a noisy recovery cannot loop
            count0=-int(self.cfg["warmup"]) * (1 + self.rollbacks),
        )
        # replaying the exact key stream after a rollback would re-draw the
        # same sample indices/noise that fed the anomaly; derive a fresh
        # deterministic stream keyed by the rollback ordinal
        reseed = getattr(self._runtime, "reseed_key_stream", None)
        if reseed is not None:
            reseed(self.rollbacks + 1)
        self.rollbacks += 1
        self.last_rollback = {
            "ckpt": os.path.basename(target),
            "at_dispatch": self.dispatches,
            "consecutive_skips": consec,
            "quarantined": quarantined,
        }
        warnings.warn(
            f"sentinel: rollback #{self.rollbacks} to {target} after {consec} consecutive "
            f"anomalous updates ({len(quarantined)} pending checkpoint(s) quarantined)"
        )
        from sheeprl_tpu.obs import flight

        flight.fleet_event(
            "sentinel_rollback",
            ckpt=os.path.basename(target),
            consecutive_skips=consec,
            rollbacks=self.rollbacks,
        )
        for fn in self._on_rollback:
            try:
                fn(target)
            except Exception:
                pass
        return state

    def _fallback_any_finite(self, scan_root: str) -> Optional[str]:
        from sheeprl_tpu.resilience.autoresume import list_checkpoints
        from sheeprl_tpu.utils.ckpt_format import (
            CheckpointCorruptError,
            spot_check_finite,
            validate_checkpoint,
        )

        for ckpt in list_checkpoints(scan_root):
            if os.path.abspath(ckpt) in self._rejected:
                continue
            try:
                validate_checkpoint(ckpt)
                spot_check_finite(ckpt)
            except CheckpointCorruptError:
                continue
            warnings.warn(
                f"sentinel: no good-tagged checkpoint yet — falling back to {ckpt} "
                "(validated + finite, re-tagged pending)"
            )
            if self._tags is not None:
                self._tags.note_save(ckpt, self.healthy_marker)
            return ckpt
        return None

    # ------------------------------------------------------------- telemetry
    def stats(self) -> Dict[str, Any]:
        """The telemetry record's ``health`` key (see howto docs)."""
        out: Dict[str, Any] = {
            "enabled": self.enabled,
            "updates": self.dispatches,
            "skips": self.skips,
            "rollbacks": self.rollbacks,
            "trips": self.trips,
            "last_ok": self.last_ok,
        }
        if self.last_z is not None:
            out["last_z"] = self.last_z
        if self.stat_keys:
            out["stats"] = list(self.stat_keys)
        if self._tags is not None:
            out["ckpt_tags"] = self._tags.stats()
        if self.last_rollback is not None:
            out["last_rollback"] = self.last_rollback
        return out

    def apply_remote(self, snapshot: Optional[Dict[str, Any]]) -> None:
        """Decoupled LEAD side: fold the trainer's health snapshot (riding
        the params broadcast) into the local tagger so the checkpoints the
        lead writes get promoted/quarantined by the trainer's verdicts."""
        if not snapshot or self._tags is None:
            return
        marker = int(snapshot.get("updates", 0)) - int(snapshot.get("skips", 0))
        if int(snapshot.get("skips", 0)) > self.skips:
            self._tags.note_anomaly(marker)
        else:
            self._tags.promote(marker, self.cfg["good_after"])
        if int(snapshot.get("trips", 0)) > self.trips:
            self._tags.quarantine_pending()
        self.dispatches = int(snapshot.get("updates", self.dispatches))
        self.skips = int(snapshot.get("skips", self.skips))
        self.trips = int(snapshot.get("trips", self.trips))
        self.rollbacks = int(snapshot.get("rollbacks", self.rollbacks))
        self.healthy_marker = marker
        self.last_ok = bool(snapshot.get("last_ok", True))


def _constrain_boundaries(runtime, update: Callable, n_state: int) -> Callable:
    """Pin the update's state outputs to the mesh's canonical layout
    (``with_sharding_constraint`` at the update boundary): on a
    multi-device mesh every returned state tree (params, opt-state,
    moments, ...) is constrained to the ZeRO ``fsdp`` layout under
    ``strategy=fsdp`` and to replicated otherwise, so the reduce-scatter/
    all-gather structure of the lowered program is explicit instead of an
    accident of GSPMD propagation.  Single-device runs return ``update``
    UNTOUCHED — the wrapped fn is the exact pre-PR traced program, which
    is what keeps the 1-device path bit-exact."""
    layout = getattr(runtime, "layout", None)
    if layout is None or runtime.world_size == 1:
        return update
    fsdp = getattr(runtime, "strategy", "") == "fsdp" and runtime.fsdp_size > 1

    def constrained(*args):
        out = update(*args)
        state_out = tuple(layout.constrain_state(t, fsdp=fsdp) for t in out[:n_state])
        return (*state_out, *out[n_state:])

    constrained.__name__ = getattr(update, "__name__", "update")
    return constrained


# ------------------------------------------------------------- the one hook
class GuardedUpdate:
    """Callable wrapper around an algo's raw update/train function — the
    single sentinel hook every update builder routes through.

    Call convention (all 13 loops follow it): the first ``n_state``
    positional args are training state (params, opt state, moments, ...),
    the update returns those same states first, then a metrics dict, then
    optional extras (e.g. SAC's |TD|).  The wrapper keeps the exact
    external signature — loops call and unpack unchanged — and exposes
    :attr:`health` for the rollback wiring.

    Disabled (default): dispatches the untouched pre-sentinel jitted
    step.  Enabled: dispatches ONE jitted program that also computes the
    monitor stats, the verdict, and the predicated state selection."""

    def __init__(self, runtime, update: Callable, cfg, *, n_state: int, donate_argnums):
        scfg = sentinel_setting(cfg)
        self._runtime = runtime
        self._update = update  # raw update: eval_shape'd for the stat keys
        # multi-device: the dispatched program additionally pins state
        # outputs to the canonical mesh layout (single-device: identity)
        update = _constrain_boundaries(runtime, update, int(n_state))
        self._n_state = int(n_state)
        self._faults = _UpdateFaults()
        self.health = TrainHealth(runtime, scfg)
        self.enabled = self.health.enabled
        if not self.enabled:
            self._fn = runtime.setup_step(update, donate_argnums=tuple(donate_argnums))
            # the FLOPs probe (benchmarks/flops_probe.py) lowers the raw
            # jitted step via this attribute — keep it reachable through
            # the wrapper (sentinel-on programs take the extra state arg,
            # so only the off path exposes it)
            self._jitted = getattr(self._fn, "_jitted", None)
            return
        knobs = {
            "z_max": scfg["z_max"],
            "ema_alpha": scfg["ema_alpha"],
            "warmup": scfg["warmup"],
            "skip_budget": scfg["skip_budget"],
        }
        n = self._n_state
        holder: Dict[str, List[str]] = {}

        def guarded(sentinel_state, *args):
            import jax
            import jax.numpy as jnp

            out = update(*args)
            state_out, metrics, rest = out[:n], out[n], out[n + 1 :]
            upd_norm = _tree_update_norm(out[0], args[0])
            vals = [
                jnp.asarray(metrics[k], jnp.float32)
                for k in holder["keys"]
                if k != "update_norm"
            ] + [upd_norm]
            ok, new_sentinel = detector_step(sentinel_state, jnp.stack(vals), **knobs)

            def sel(new_leaf, old_leaf):
                return jnp.where(ok, new_leaf, old_leaf)

            selected = tuple(
                jax.tree_util.tree_map(sel, s_new, s_old)
                for s_new, s_old in zip(state_out, args[:n])
            )
            layout = getattr(runtime, "layout", None)
            if layout is not None and runtime.world_size > 1:
                # the verdict state must stay REPLICATED on the mesh: the
                # host polls it every check_every dispatches, and a sharded
                # (or device-0-pinned) layout would turn that poll into a
                # cross-device fetch on the hot path (asserted by tests)
                new_sentinel = SentinelState(*(
                    jax.lax.with_sharding_constraint(leaf, layout.replicated)
                    for leaf in new_sentinel
                ))
            return (new_sentinel, *selected, metrics, *rest)

        self._holder = holder
        self._fn = runtime.setup_step(
            guarded, donate_argnums=(0,) + tuple(d + 1 for d in donate_argnums)
        )

    # ------------------------------------------------------------- stat keys
    def _resolve_stat_keys(self, args) -> List[str]:
        """Trace the raw update abstractly once to learn which scalar
        metrics exist (``Loss/*`` and ``Grads/*``); the stats vector is
        those plus the param-update norm.  eval_shape is free (no
        compilation, no dispatch)."""
        import jax

        shapes = jax.eval_shape(self._update, *args)
        metrics = shapes[self._n_state]
        keys = sorted(
            k
            for k, v in metrics.items()
            if k.startswith(("Loss/", "Grads/")) and getattr(v, "shape", None) == ()
        )
        return keys + ["update_norm"]

    def _note_mesh_telemetry(self, args) -> None:
        """First-dispatch hook: stash the mesh layout extras (param bytes,
        achieved FSDP shard bytes, opt-in collective-bytes estimate) on the
        runtime so ``MeshRuntime.mesh_telemetry`` — the telemetry record's
        ``mesh`` key — reports them without the loops threading params
        through the observability layer."""
        runtime = self._runtime
        layout = getattr(runtime, "layout", None)
        if layout is None or getattr(runtime, "_mesh_extra", None) is not None:
            return
        try:
            extra: Dict[str, Any] = {
                "param_bytes_total": int(runtime._player_params_nbytes(args[0]))
            }
            if getattr(runtime, "strategy", "") == "fsdp" and runtime.fsdp_size > 1:
                extra["param_bytes_per_device"] = layout.param_shard_bytes(args[0])
            if not self.enabled and os.environ.get(
                "SHEEPRL_MESH_COST_TELEMETRY", ""
            ).strip() in ("1", "true", "on"):
                # opt-in: one AOT lower+compile of the update (hits the
                # persistent compilation cache when armed) for the
                # cross-device traffic estimate from cost_analysis();
                # sentinel-on programs take the extra state arg, so only
                # the off path can lower from the raw update args
                jitted = getattr(self._fn, "_jitted", None)
                if jitted is not None:
                    from sheeprl_tpu.parallel.sharding import collective_bytes_estimate

                    est = collective_bytes_estimate(jitted.lower(*args).compile())
                    if est is not None:
                        extra["collective_bytes_estimate"] = est
            runtime._mesh_extra = extra
        except Exception:
            runtime._mesh_extra = {}

    def __call__(self, *args):
        args = self._faults.apply(args, self._n_state)
        if not self.enabled:
            self._note_mesh_telemetry(args)
            return self._fn(*args)
        if self.health.device_state is None:
            self._note_mesh_telemetry(args)
            keys = self._resolve_stat_keys(args)
            self._holder["keys"] = keys
            self.health.stat_keys = keys
            self.health.device_state = init_sentinel_state(len(keys))
        out = self._fn(self.health.device_state, *args)
        self.health.device_state = out[0]
        self.health.note_dispatch()
        # start the tiny verdict copies early so tick()'s device_get rides
        # under the update's own completion instead of stalling after it
        from sheeprl_tpu.utils.utils import start_async_host_copy

        st = out[0]
        start_async_host_copy(st.last_ok, st.consec_skips, st.total_skips, st.tripped, st.last_z)
        return out[1:]


def guard_update(runtime, update: Callable, cfg, *, n_state: int = 2, donate_argnums=(0, 1)):
    """The shared builder hook: every algo's ``make_update_fn`` /
    ``make_train_fn`` tail-calls this instead of ``runtime.setup_step``.
    Returns a :class:`GuardedUpdate` whose call signature and outputs are
    identical to the raw jitted step, with ``.health`` attached."""
    return GuardedUpdate(runtime, update, cfg, n_state=n_state, donate_argnums=donate_argnums)
