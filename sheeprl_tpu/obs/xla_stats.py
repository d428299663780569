"""XLA-level statistics: recompile detection, compile-cache counters, and
a generic MFU/FLOPs reporter.

Recompiles are THE silent TPU performance killer: a jitted train step that
retraces after warmup (a shape drift, a new dtype, a python-object leak
into the trace) pays seconds of XLA compile per occurrence and invalidates
every steady-state throughput number. ``jax.monitoring`` emits an event
for every backend compile and for every persistent-compilation-cache
interaction; ``RecompileMonitor`` listens to those, and once the caller
marks warmup complete, each further compile is recorded and WARNed — the
counter also feeds the telemetry JSONL so a post-hoc reader can see exactly
when a run started retracing.

The MFU reporter generalizes bench.py's hand-rolled DV3-only math: FLOPs
come from ``Compiled.cost_analysis()`` of any jitted function, the peak
from a device-kind table (overridable with ``SHEEPRL_PEAK_FLOPS``); an
unknown device has no peak.
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Any, Dict, Optional

# the events jax 0.9.0 emits (jax/_src/dispatch.py, jax/_src/compiler.py),
# by their full names: tests/test_utils/test_xla_stats.py compiles a
# function and fails if any counter stays 0, so a rename shows as a test
# failure and not as a silent zero
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_lock = threading.Lock()
_monitors: list = []  # active RecompileMonitor instances
_listeners_installed = False


def _dispatch_event(event: str, **kwargs: Any) -> None:
    with _lock:
        active = list(_monitors)
    for m in active:
        m._on_event(event)


def _dispatch_duration(event: str, duration_secs: float, **kwargs: Any) -> None:
    with _lock:
        active = list(_monitors)
    for m in active:
        m._on_duration(event, duration_secs)


def _install_listeners() -> None:
    """Register the module-level jax.monitoring listeners exactly once.

    jax.monitoring has no unregister API (only a global clear), so a single
    pair of listeners dispatches to whatever monitors are currently active;
    monitors subscribe/unsubscribe from the module-level list instead.
    """
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return
        _listeners_installed = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_dispatch_event)
    jax.monitoring.register_event_duration_secs_listener(_dispatch_duration)


class RecompileMonitor:
    """Counts XLA compiles / trace time / compile-cache traffic, and flags
    compiles that happen after warmup (= retraces of supposedly-stable
    jitted functions).

    Usage::

        mon = RecompileMonitor().install()
        ...  # build + first calls of all jitted steps
        mon.mark_warmup_complete()
        ...  # any further compile -> one warning each + counted
        mon.uninstall()

    Thread-safe; multiple monitors can be active (each keeps its own
    counters). ``snapshot()`` returns a JSON-ready dict for telemetry.
    """

    def __init__(self, name: str = "run", warn: bool = True):
        self.name = name
        self.warn = warn
        self.compiles = 0
        self.compile_time_s = 0.0
        self.trace_time_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.post_warmup_compiles = 0
        self.post_warmup_compile_time_s = 0.0
        self._warmup_done = False
        self._installed = False

    # ---------------------------------------------------------- lifecycle
    def install(self) -> "RecompileMonitor":
        if not self._installed:
            _install_listeners()
            with _lock:
                _monitors.append(self)
            self._installed = True
        return self

    def uninstall(self) -> None:
        if self._installed:
            with _lock:
                if self in _monitors:
                    _monitors.remove(self)
            self._installed = False

    def mark_warmup_complete(self) -> None:
        self._warmup_done = True

    @property
    def warmed_up(self) -> bool:
        return self._warmup_done

    # ---------------------------------------------------------- listeners
    def _on_event(self, event: str) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1
        elif event == CACHE_MISS_EVENT:
            self.cache_misses += 1

    def _on_duration(self, event: str, duration_secs: float) -> None:
        if event == TRACE_EVENT:
            self.trace_time_s += duration_secs
            return
        if event != COMPILE_EVENT:
            return
        self.compiles += 1
        self.compile_time_s += duration_secs
        if self._warmup_done:
            self.post_warmup_compiles += 1
            self.post_warmup_compile_time_s += duration_secs
            if self.warn:
                warnings.warn(
                    f"[{self.name}] XLA recompile #{self.post_warmup_compiles} after warmup "
                    f"({duration_secs:.3f}s compile). A jitted step is retracing — look for "
                    "shape/dtype drift or python objects leaking into traced code "
                    "(run with JAX_LOG_COMPILES=1 to see which function).",
                    RuntimeWarning,
                    stacklevel=2,
                )

    # ---------------------------------------------------------- reporting
    def snapshot(self) -> Dict[str, Any]:
        return {
            "total": self.compiles,
            "compile_time_s": round(self.compile_time_s, 3),
            "trace_time_s": round(self.trace_time_s, 3),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "post_warmup": self.post_warmup_compiles,
            "post_warmup_compile_time_s": round(self.post_warmup_compile_time_s, 3),
        }


# --------------------------------------------------------------------- MFU
# peak dense FLOP/s per chip by device kind (bf16 matmul peak — the unit
# every published TPU MFU number uses). Matched case-insensitively by
# substring of jax's Device.device_kind.
_PEAK_FLOPS_BY_DEVICE_KIND = {
    "tpu v5 lite": 197e12,  # v5e
    "tpu v5e": 197e12,
    "tpu v5p": 459e12,
    "tpu v5": 459e12,  # plain "TPU v5" reports as v5p
    "tpu v6 lite": 918e12,  # v6e / Trillium
    "tpu v6e": 918e12,
    "tpu v4": 275e12,
    "tpu v3": 123e12,
    "tpu v2": 45e12,
}


def peak_flops(device: Optional[Any] = None) -> Optional[float]:
    """Peak dense bf16 FLOP/s of one chip, or None when the device kind
    is not in the table (CPU, new hardware) — never another chip's peak.
    ``SHEEPRL_PEAK_FLOPS`` overrides the table."""
    env = os.environ.get("SHEEPRL_PEAK_FLOPS")
    if env:
        return float(env)
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(getattr(device, "device_kind", "")).lower()
    for marker, peak in _PEAK_FLOPS_BY_DEVICE_KIND.items():
        if marker in kind:
            return peak
    return None


def compiled_flops(compiled: Any) -> Optional[float]:
    """FLOPs of one execution of a ``Compiled`` object (from
    ``jitted.lower(...).compile()``), via XLA cost analysis. None when the
    analysis reports no flops; a failing analysis raises."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return float((ca or {}).get("flops", 0.0)) or None


def mfu_percent(
    flops_per_step: Optional[float],
    step_seconds: float,
    device: Optional[Any] = None,
    peak: Optional[float] = None,
) -> Optional[float]:
    """Model FLOPs Utilization in percent: achieved FLOP/s over the chip's
    peak. None when FLOPs or the peak are unknown — callers must treat MFU
    as best-effort (a CPU has no meaningful peak)."""
    if not flops_per_step or step_seconds <= 0:
        return None
    peak = peak if peak is not None else peak_flops(device)
    if not peak:
        return None
    return 100.0 * flops_per_step / step_seconds / peak
