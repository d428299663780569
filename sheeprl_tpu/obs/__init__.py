"""sheeprl_tpu.obs — the framework-wide TPU-native observability layer.

Four parts (ISSUE 1):

- :mod:`sheeprl_tpu.obs.trace` — jax.profiler phase annotations + windowed
  on-demand trace capture (``metric.profile_every_n``);
- :mod:`sheeprl_tpu.obs.xla_stats` — recompile detection, compile-cache
  counters, generic MFU/FLOPs reporting;
- :mod:`sheeprl_tpu.obs.telemetry` — the append-only JSONL run-telemetry
  sink every algo feeds per log interval;
- :class:`Observability` (here) — the per-run orchestrator the algo loops
  wire in with three calls: ``on_iteration`` (profiler scheduling, cheap
  integer work), ``on_log`` (assemble + append one telemetry record), and
  ``close``.

``setup_observability`` returns a disabled no-op instance on non-zero
ranks / ``metric.log_level=0`` / ``metric.telemetry=False``, so call
sites stay unconditional.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

from sheeprl_tpu.obs import fleet, flight, ledger
from sheeprl_tpu.obs.flight import FlightRecorder, fleet_event, tracing_setting
from sheeprl_tpu.obs.ledger import TimeLedger, ledger_setting
from sheeprl_tpu.obs.telemetry import (
    TelemetrySink,
    device_memory_stats,
    host_rss_mb,
    make_record,
    read_records,
    validate_record,
)
from sheeprl_tpu.obs.trace import ProfileScheduler, start_trace, stop_trace, trace_scope
from sheeprl_tpu.obs.xla_stats import RecompileMonitor, compiled_flops, mfu_percent, peak_flops

__all__ = [
    "FlightRecorder",
    "Observability",
    "fleet",
    "fleet_event",
    "flight",
    "ledger",
    "ledger_setting",
    "setup_observability",
    "TimeLedger",
    "trace_scope",
    "tracing_setting",
    "start_trace",
    "stop_trace",
    "ProfileScheduler",
    "RecompileMonitor",
    "TelemetrySink",
    "compiled_flops",
    "mfu_percent",
    "peak_flops",
    "device_memory_stats",
    "host_rss_mb",
    "make_record",
    "read_records",
    "validate_record",
]


class Observability:
    """Per-run observability: owns the telemetry sink, the recompile
    monitor and the profile scheduler. All methods are no-ops when
    ``enabled`` is False, so algo loops call them unconditionally."""

    def __init__(
        self,
        enabled: bool = False,
        telemetry_path: Optional[str] = None,
        telemetry_max_bytes: int = 32 * 1024 * 1024,
        profile_dir: Optional[str] = None,
        profile_every_n: int = 0,
        profile_num_iters: int = 2,
        world_size: int = 1,
        action_repeat: int = 1,
        device: Any = None,
        logger: Any = None,
        name: str = "run",
    ):
        self.enabled = bool(enabled)
        self.recompile: Optional[RecompileMonitor] = None
        self.scheduler: Optional[ProfileScheduler] = None
        self.sink: Optional[TelemetrySink] = None
        # zero-arg provider of checkpoint write/stall stats; the
        # CheckpointManager (resilience/manager.py) attaches itself here so
        # every telemetry record carries a "ckpt" section
        self.ckpt_stats: Optional[Any] = None
        # zero-arg provider of training-health stats; the sentinel's
        # TrainHealth (resilience/sentinel.py) attaches itself here so the
        # records carry a "health" section (verdicts, skip/rollback
        # counters, z-scores)
        self.health_stats: Optional[Any] = None
        # zero-arg provider of inference-serving stats; the serve client
        # and/or server (serve/) attach here so the records carry a
        # "serve" section (p50/p95 latency, queue depth, batch-size
        # histogram, breaker state, dedupe/audit counters)
        self.serve_stats: Optional[Any] = None
        # zero-arg provider of device-resident env stats; the fused
        # collectors (envs/jax/collect.py) attach here so the records
        # carry a "jaxenv" section (backend, env family, env-step and
        # episode-event counters) when algo.env_backend=jax
        self.jaxenv_stats: Optional[Any] = None
        # zero-arg provider of mesh-layout stats (axis names/sizes, FSDP
        # param-shard bytes, per-update collective-bytes estimate);
        # setup_observability wires MeshRuntime.mesh_telemetry here so
        # every record carries a "mesh" section (howto/observability.md)
        self.mesh_stats: Optional[Any] = None
        # zero-arg provider of the player's placement (device, weight
        # bytes) and the bytes its weight refreshes copied across backends
        # since the last record; setup_observability wires
        # MeshRuntime.player_telemetry here ("player" section; None until a
        # loop placed its player)
        self.player_stats: Optional[Any] = None
        if not self.enabled:
            return
        self._world_size = max(1, int(world_size))
        self._action_repeat = max(1, int(action_repeat))
        self._device = device
        self._logger = logger
        self._last_step = 0
        self._last_train = 0
        self._last_ts = time.perf_counter()
        self.recompile = RecompileMonitor(name=name).install()
        if telemetry_path:
            # metric.live=off: fleet.make_sink returns the UNDECORATED
            # TelemetrySink (type identity, zero overhead); live=on tees
            # every record into this process's MetricsHub + alert rules
            self.sink = fleet.make_sink(telemetry_path, max_bytes=telemetry_max_bytes)
        if profile_dir and profile_every_n > 0:
            self.scheduler = ProfileScheduler(profile_dir, profile_every_n, profile_num_iters)

    # ------------------------------------------------------------- hooks
    def on_iteration(self, policy_step: int = 0) -> None:
        """Once per training iteration: drives windowed trace capture."""
        if self.enabled and self.scheduler is not None:
            self.scheduler.on_iteration()

    def on_log(
        self,
        policy_step: int,
        train_step: int = 0,
        train_time_s: Optional[float] = None,
        extra: Optional[Dict[str, Any]] = None,
    ) -> Optional[Dict[str, Any]]:
        """Once per log interval, BEFORE ``timer.reset()``: assembles and
        appends one telemetry record. Returns the record (for tests)."""
        if not self.enabled:
            return None
        from sheeprl_tpu.utils.timer import timer

        timers = {} if timer.disabled else timer.compute()
        percentiles = {} if timer.disabled else timer.percentiles()
        now = time.perf_counter()
        wall = now - self._last_ts
        d_step = policy_step - self._last_step
        d_train = train_step - self._last_train
        train_time = (
            train_time_s if train_time_s is not None else timers.get("Time/train_time", 0.0)
        )
        env_time = timers.get("Time/env_interaction_time", 0.0)
        if self.ckpt_stats is not None:
            try:
                extra = {**(extra or {}), "ckpt": self.ckpt_stats()}
            except Exception:
                pass
        if self.health_stats is not None:
            try:
                extra = {**(extra or {}), "health": self.health_stats()}
            except Exception:
                pass
        if self.serve_stats is not None:
            try:
                extra = {**(extra or {}), "serve": self.serve_stats()}
            except Exception:
                pass
        if self.jaxenv_stats is not None:
            try:
                extra = {**(extra or {}), "jaxenv": self.jaxenv_stats()}
            except Exception:
                pass
        if self.mesh_stats is not None:
            try:
                extra = {**(extra or {}), "mesh": self.mesh_stats()}
            except Exception:
                pass
        if self.player_stats is not None:
            try:
                player = self.player_stats()
                if player is not None:
                    extra = {**(extra or {}), "player": player}
            except Exception:
                pass
        led = ledger.get_ledger()
        if led is not None:
            # the streaming time ledger's breakdown rides every record
            # under "where" (ISSUE 16) — derived at record time, no
            # post-hoc pass over the flight stream
            try:
                extra = {**(extra or {}), "where": led.snapshot()}
            except Exception:
                pass
        recorder = flight.get_recorder()
        if recorder is not None:
            # flight-recorder counters ride the telemetry under "trace",
            # and the log cadence doubles as the recorder's flush beat
            try:
                extra = {**(extra or {}), "trace": recorder.stats()}
                recorder.flush()
            except Exception:
                pass
        record = make_record(
            step=policy_step,
            train_step=train_step,
            sps=(d_step / wall) if wall > 0 and d_step > 0 else None,
            sps_env=(
                (d_step / self._world_size * self._action_repeat) / env_time
                if env_time > 0 and d_step > 0
                else None
            ),
            sps_train=(d_train / train_time) if train_time > 0 and d_train > 0 else None,
            timers_s=timers,
            timer_percentiles_s=percentiles,
            hbm=device_memory_stats(self._device),
            host_rss=host_rss_mb(),
            compiles=self.recompile.snapshot() if self.recompile else {},
            extra=extra,
        )
        if self.sink is not None:
            self.sink.write(record)
        if self._logger is not None:
            self._mirror_to_logger(record, policy_step)
        # retraces are only suspicious once every jitted step has been
        # built.  The first training iteration compiles the update; the
        # next ones still compile first uses (the player's policy step
        # after learning_starts, the steady-size replay feed, the
        # episode-end ring write — 11 such compiles on the first DV3-S chip
        # run).  So warm-up ends with the first log interval that BEGAN
        # with training already dispatched.
        if self.recompile and not self.recompile.warmed_up and self._last_train > 0:
            self.recompile.mark_warmup_complete()
        self._last_step = policy_step
        self._last_train = train_step
        self._last_ts = now
        return record

    def _mirror_to_logger(self, record: Dict[str, Any], step: int) -> None:
        """Mirror the load-bearing scalars to the metrics logger so TPU
        health is visible in TensorBoard next to the losses."""
        scalars: Dict[str, float] = {}
        compiles = record.get("compiles") or {}
        if "total" in compiles:
            scalars["Obs/compiles_total"] = compiles["total"]
            scalars["Obs/compiles_post_warmup"] = compiles.get("post_warmup", 0)
        hbm = record.get("hbm") or {}
        if "bytes_in_use" in hbm:
            scalars["Obs/hbm_gb_in_use"] = hbm["bytes_in_use"] / 1e9
        if record.get("host_rss_mb") is not None:
            scalars["Obs/host_rss_mb"] = record["host_rss_mb"]
        for name, pct in (record.get("timer_percentiles_s") or {}).items():
            for q in ("p50", "p95"):
                if q in pct:
                    scalars[f"{name}_{q}"] = pct[q]
        if scalars:
            self._logger.log_metrics(scalars, step)

    def flush(self) -> None:
        """fsync buffered telemetry lines (preemption/emergency paths)."""
        if self.enabled and self.sink is not None:
            self.sink.flush()
        recorder = flight.get_recorder()
        if recorder is not None:
            recorder.flush()

    def close(self) -> None:
        if not self.enabled:
            return
        if self.scheduler is not None:
            self.scheduler.close()
        if self.sink is not None:
            self.sink.close()
        if self.recompile is not None:
            self.recompile.uninstall()
        # the live plane outlives the sink only until run teardown: a
        # sequential in-process run (bench legs, chaos soak) must not
        # inherit the previous run's hub/alert state or endpoint
        fleet.close_live()
        # same for the time ledger — its window must open per run
        ledger.close_ledger()


def setup_observability(runtime, cfg, log_dir: Optional[str], logger: Any = None) -> Observability:
    """Build the run's Observability from ``cfg.metric``. Rank-0 only (each
    process observes itself; the decoupled player wires its own)."""
    metric_cfg = cfg.get("metric", {}) if hasattr(cfg, "get") else {}
    # live metrics plane (ISSUE 15): like the flight recorder, the first
    # configure sticks — decoupled players/trainers install their own
    # role BEFORE calling this, so "main" only lands on coupled loops.
    # Constructed before the enabled gate: the plane still serves the
    # /status endpoint when this process owns no telemetry sink.
    if runtime.is_global_zero and fleet.get_live() is None and fleet.live_setting(cfg):
        fleet.configure_from_cfg(cfg, role="main")
    # time ledger (ISSUE 16): same first-configure-sticks pattern — the
    # decoupled roles install theirs before reaching this call.  Every
    # rank ledgers itself (cheap, in-memory, no endpoint).
    if ledger.get_ledger() is None and ledger.ledger_setting(cfg):
        ledger.configure(role="main" if runtime.is_global_zero else f"rank{getattr(runtime, 'global_rank', 0)}")
    enabled = (
        runtime.is_global_zero
        and log_dir is not None
        and int(metric_cfg.get("log_level", 1)) > 0
        and bool(metric_cfg.get("telemetry", True))
    )
    if not enabled:
        return Observability(enabled=False)
    profile_dir = metric_cfg.get("profile_dir") or os.path.join(log_dir, "profile")
    # the whole-run metric.profile trace (cli.py) and the windowed scheduler
    # cannot nest — the flag wins
    every_n = 0 if metric_cfg.get("profile", False) else int(metric_cfg.get("profile_every_n", 0) or 0)
    obs = Observability(
        enabled=True,
        telemetry_path=os.path.join(log_dir, "telemetry.jsonl"),
        telemetry_max_bytes=int(metric_cfg.get("telemetry_max_bytes", 32 * 1024 * 1024)),
        profile_dir=profile_dir,
        profile_every_n=every_n,
        profile_num_iters=int(metric_cfg.get("profile_num_iters", 2)),
        world_size=runtime.world_size,
        action_repeat=int(cfg.env.get("action_repeat", 1)) if "env" in cfg else 1,
        device=runtime.device,
        # TB mirroring of the telemetry scalars is opt-in: every extra
        # add_scalar series costs event-file traffic per log interval, and
        # the JSONL is the canonical consumer
        logger=logger if metric_cfg.get("telemetry_tb_mirror", False) else None,
        name=str(cfg.get("algo", {}).get("name", "run")),
    )
    obs.mesh_stats = getattr(runtime, "mesh_telemetry", None)
    obs.player_stats = getattr(runtime, "player_telemetry", None)
    # flight recorder (ISSUE 13): the coupled loops get their process
    # recorder here (role "main"); the decoupled loops configure their
    # own role BEFORE calling this, which wins — first configure sticks
    if flight.get_recorder() is None and tracing_setting(cfg) != "off":
        flight.configure_from_cfg(cfg, role="main")
    return obs
