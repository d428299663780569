"""CheckpointManager — the one checkpoint path every algo loop shares.

Before this module, every algorithm carried its own copy of the cadence
check + state-dict assembly + ``CheckpointCallback.save`` call (13 nearly
identical blocks). The manager centralizes:

- **cadence**: ``checkpoint.every`` policy-step intervals, ``save_last``
  on the final iteration, and a forced save when a preemption signal is
  pending — one ``maybe_checkpoint`` call per iteration;
- **async writing** (``checkpoint.async_save``): the in-loop cost drops to
  the fast snapshot (device→host + buffer materialization); manifest
  encoding and the zip write move to the
  :class:`~sheeprl_tpu.resilience.async_writer.AsyncCheckpointWriter`
  background thread, with an end-of-run :meth:`close` barrier;
- **preemption**: owns the process's
  :class:`~sheeprl_tpu.resilience.preemption.PreemptionHandler`; loops
  check :attr:`preempted` after ``maybe_checkpoint`` and break — the
  forced save has already produced a fully resumable checkpoint;
- **telemetry**: in-loop stall seconds vs total write seconds are exposed
  through :meth:`stats` and ride the run's ``telemetry.jsonl`` (PR-1
  observability sink), so resilience overhead is measurable, not folklore.

``state_fn`` is a zero-arg callable building the state dict — evaluated
only when a save actually happens, on rank zero, after
``last_checkpoint`` has been advanced (so the dict can embed
``mgr.last_checkpoint``).
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional

from sheeprl_tpu.resilience.async_writer import AsyncCheckpointWriter
from sheeprl_tpu.resilience.preemption import PreemptionHandler
from sheeprl_tpu.utils.callback import CheckpointCallback
from sheeprl_tpu.utils.timer import timer


class NonFiniteCheckpointError(RuntimeError):
    """A checkpoint save was refused because the agent params contain
    non-finite values (``checkpoint.allow_nonfinite=false``, the default):
    persisting NaN/inf weights turns one bad update into a poisoned
    resume point that ``resume_from=auto`` would ride forever."""

    def __init__(self, path: str, bad_leaves):
        self.path = str(path)
        self.bad_leaves = list(bad_leaves)
        shown = ", ".join(self.bad_leaves[:5])
        more = f" (+{len(self.bad_leaves) - 5} more)" if len(self.bad_leaves) > 5 else ""
        super().__init__(
            f"refusing to save non-finite params to {self.path}: offending leaves "
            f"[{shown}]{more}; fix the divergence (or enable the training sentinel, "
            "algo.sentinel.enabled=true) — set checkpoint.allow_nonfinite=true only "
            "to capture a post-mortem snapshot on purpose"
        )


def _nonfinite_leaves(tree) -> list:
    """Dot-paths of non-finite float leaves in a host (numpy) pytree."""
    import jax
    import numpy as np

    bad = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        try:
            arr = np.asarray(leaf)
        except Exception:
            continue
        if arr.dtype.kind == "f" and not np.isfinite(arr).all():
            bad.append(jax.tree_util.keystr(path))
    return bad


class CheckpointManager:
    def __init__(
        self,
        runtime,
        cfg,
        log_dir: Optional[str],
        observability: Any = None,
        last_checkpoint: int = 0,
        forward_preemption_to: Optional[list] = None,
    ):
        ckpt_cfg = cfg.checkpoint
        self._runtime = runtime
        self.every = int(ckpt_cfg.every)
        self.save_last = bool(ckpt_cfg.save_last)
        self.async_save = bool(ckpt_cfg.get("async_save", True))
        self.allow_nonfinite = bool(ckpt_cfg.get("allow_nonfinite", False))
        # checkpoint.sharded: write `.dckpt` directories (per-fsdp-shard
        # parallel writes + manifest-commits-last, sharded_ckpt.py)
        # instead of the single-process zip — the shard count is the live
        # mesh's fsdp axis, so shard files mirror the device layout
        self.sharded = bool(ckpt_cfg.get("sharded", False))
        self.log_dir = log_dir
        # training-health sentinel hook (resilience/sentinel.py): when a
        # TrainHealth binds itself here, every save is tagged in the
        # good/pending/quarantined sidecar
        self.health = None
        self.last_checkpoint = int(last_checkpoint)
        self.cb = CheckpointCallback(
            keep_last=ckpt_cfg.keep_last,
            device_digests=bool(ckpt_cfg.get("device_digests", False)),
            fsdp_size=int(getattr(runtime, "fsdp_size", 1)) if self.sharded else 1,
        )
        self.writer = (
            AsyncCheckpointWriter(self.cb.write)
            if self.async_save and runtime.is_global_zero
            else None
        )
        self.preemption = PreemptionHandler(forward_to=forward_preemption_to).install()
        # --- stats (telemetry)
        self.saves = 0
        self.last_stall_s = 0.0
        self.total_stall_s = 0.0
        self._sync_write_s = 0.0
        self._observability = observability
        if observability is not None:
            observability.ckpt_stats = self.stats

    # --------------------------------------------------------------- flags
    @property
    def preempted(self) -> bool:
        return self.preemption.preempted

    def should_checkpoint(self, policy_step: int, is_last: bool = False) -> bool:
        """Cadence check, preemption included. Pure — does not advance
        ``last_checkpoint`` (that happens in :meth:`checkpoint_now`)."""
        return (
            (self.every > 0 and policy_step - self.last_checkpoint >= self.every)
            or (is_last and self.save_last)
            or self.preempted
        )

    # --------------------------------------------------------------- saves
    def ckpt_path(self, policy_step: int) -> str:
        suffix = "dckpt" if self.sharded else "ckpt"
        return os.path.join(
            self.log_dir or ".",
            "checkpoint",
            f"ckpt_{policy_step}_{self._runtime.global_rank}.{suffix}",
        )

    def maybe_checkpoint(
        self,
        *,
        policy_step: int,
        is_last: bool,
        state_fn: Callable[[], Dict[str, Any]],
    ) -> Optional[str]:
        """The per-iteration call every algo loop makes. Returns the
        checkpoint path when a save was (or started being) written."""
        if not self.should_checkpoint(policy_step, is_last):
            return None
        return self.checkpoint_now(policy_step=policy_step, state_fn=state_fn)

    def checkpoint_now(
        self, *, policy_step: int, state_fn: Callable[[], Dict[str, Any]]
    ) -> Optional[str]:
        """Unconditional save at ``policy_step`` (cadence state advances on
        every rank so multi-process cadences stay in lockstep; only global
        rank zero touches disk)."""
        self.last_checkpoint = policy_step
        if not self._runtime.is_global_zero:
            return None
        from sheeprl_tpu.obs import flight

        path = self.ckpt_path(policy_step)
        t0 = time.perf_counter()
        # the loop's stall for a save (the snapshot's device-to-host fetch, then the hand-over or the write) as a
        # root span of the iteration that saves, for every loop: ``timers_s`` and, under a profiler, the trace
        with timer("Time/checkpoint"), flight.span("ckpt_write", step=policy_step, async_save=self.async_save):
            host_state = self.cb.snapshot(state_fn())
            if not self.allow_nonfinite and "agent" in host_state:
                bad = _nonfinite_leaves(host_state["agent"])
                if bad:
                    raise NonFiniteCheckpointError(path, bad)
            if self.writer is not None:
                self.writer.submit(path, host_state)
            else:
                self.cb.write(path, host_state)
                self._sync_write_s += time.perf_counter() - t0
        self.last_stall_s = time.perf_counter() - t0
        self.total_stall_s += self.last_stall_s
        self.saves += 1
        if self.health is not None:
            self.health.note_checkpoint(path)
        if self.preempted:
            # crash-safe telemetry: the forced pre-exit save is the last
            # chance to land the tail records that explain the shutdown
            self._flush_telemetry()
        return path

    def _flush_telemetry(self) -> None:
        obs = self._observability
        if obs is not None and hasattr(obs, "flush"):
            try:
                obs.flush()
            except Exception:
                pass

    def emergency_dump(self, policy_step: int, state: Dict[str, Any]) -> Optional[str]:
        """Best-effort synchronous dump of whatever state the caller still
        owns (peer death: the full resumable state may be unreachable).
        Named ``emergency_*.ckpt`` so auto-resume and keep-last retention
        never treat a partial state as a resume point."""
        if not self._runtime.is_global_zero:
            return None
        from sheeprl_tpu.utils.ckpt_format import save_state

        path = os.path.join(
            self.log_dir or ".",
            "checkpoint",
            f"emergency_{policy_step}_{self._runtime.global_rank}.ckpt",
        )
        # the post-mortem depends on the telemetry tail more than on this
        # dump succeeding — fsync the buffered records first
        self._flush_telemetry()
        try:
            if self.writer is not None:
                self.writer.wait()
            save_state(path, self.cb.snapshot(state))
            return path
        except Exception as e:  # the original error must stay the headline
            import warnings

            warnings.warn(f"emergency checkpoint failed: {type(e).__name__}: {e}")
            return None

    # --------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        """Telemetry payload: loop stall vs background write seconds;
        sharded saves add the per-shard write seconds and the manifest
        stitch seconds of the latest committed checkpoint."""
        out: Dict[str, Any] = {
            "async": self.async_save,
            "saves": self.saves,
            "last_stall_s": round(self.last_stall_s, 6),
            "total_stall_s": round(self.total_stall_s, 6),
        }
        if self.writer is not None:
            w = self.writer.stats()
            out["last_write_s"] = w["last_write_s"]
            out["total_write_s"] = w["total_write_s"]
        else:
            out["last_write_s"] = round(self.last_stall_s, 6)
            out["total_write_s"] = round(self._sync_write_s, 6)
        if self.sharded:
            out["sharded"] = True
            s = self.cb.last_sharded_stats
            if s is not None:
                out["shards"] = s["shards"]
                out["last_shard_write_s"] = s["shard_write_s"]
                out["last_max_shard_write_s"] = s["max_shard_write_s"]
                out["last_stitch_s"] = s["stitch_s"]
            out["total_stitch_s"] = round(self.cb.total_stitch_s, 6)
        return out

    # --------------------------------------------------------------- close
    def close(self) -> None:
        """End-of-run barrier: the last async write must be fully on disk
        before the run reports success; signal handlers are restored."""
        if self.writer is not None:
            self.writer.wait()
        self.preemption.uninstall()
