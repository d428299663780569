"""Wall-clock timer context/decorator accumulating into metrics
(reference sheeprl/utils/timer.py:16-83).

Used around env interaction and train steps to derive ``Time/sps_*``
throughputs. ``timer.disabled`` turns all timing into no-ops. On TPU the
train step is async-dispatched, so timed regions must end with a
``block_until_ready`` (the algorithms do this on their final loss) for the
numbers to mean anything.

Beyond the reference's behavior, every timed region:

- keeps a bounded reservoir of raw durations so ``timer.percentiles()``
  can report p50/p95 per name — tail latency (one retracing iteration, a
  GC pause, an env hiccup) is invisible in the sums;
- is wrapped in a ``jax.profiler`` TraceAnnotation, so whenever a
  profiler trace is active (``metric.profile`` / ``profile_every_n``)
  the phases appear as named spans on the host timeline for free.

Regions nest by time on one thread (DreamerV3's loop: ``Time/player_step``,
``Time/replay_add`` and ``Time/env_step`` inside
``Time/env_interaction_time``); each name sums its own regions, so a parent's
self time is its sum minus its children's.  A region that is open across
``timer.reset()`` — the loop's ``Time/log`` holds the reset of its own
interval, and closes after ``Observability.on_log`` has read the sums —
registers again when it closes: its time is counted in the NEXT interval's
record.  Right for shares over many records, one interval late in any one.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import ContextDecorator
from typing import Any, Deque, Dict, Sequence, Type

from sheeprl_tpu.utils.metric import Metric, SumMetric

try:  # annotation is optional: timing must work even without a profiler
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover - only hit on broken jax installs
    _TraceAnnotation = None


class timer(ContextDecorator):
    disabled: bool = False
    timers: Dict[str, Metric] = {}
    samples: Dict[str, Deque[float]] = {}
    # raw-duration reservoir per name; at one train + one env region per
    # policy step this covers well past a log interval of history
    max_samples: int = 4096

    def __init__(self, name: str, metric_cls: Type[Metric] = SumMetric, **metric_kwargs: Any):
        self.name = name
        self._metric_cls = metric_cls
        self._metric_kwargs = metric_kwargs
        self._register()

    def _register(self) -> None:
        if not timer.disabled and self.name not in timer.timers:
            timer.timers[self.name] = self._metric_cls(**self._metric_kwargs)

    def __enter__(self) -> "timer":
        if not timer.disabled:
            # lazily re-register: a timer instance (incl. decorator use)
            # outlives timer.reset(), which drops the metric registered in
            # __init__ — without this, __exit__ dies with a KeyError
            self._register()
            self._annotation = _TraceAnnotation(self.name) if _TraceAnnotation else None
            if self._annotation is not None:
                self._annotation.__enter__()
            self._start = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> bool:
        if not timer.disabled:
            elapsed = time.perf_counter() - self._start
            if self._annotation is not None:
                self._annotation.__exit__(*exc)
                self._annotation = None
            # a region open across timer.reset() (the loop's Time/log holds
            # the reset itself) finds its metric dropped: register again, so
            # its time lands in the next interval's sums
            self._register()
            timer.timers[self.name].update(elapsed)
            buf = timer.samples.get(self.name)
            if buf is None:
                buf = timer.samples[self.name] = deque(maxlen=timer.max_samples)
            buf.append(elapsed)
        return False

    @classmethod
    def compute(cls) -> Dict[str, float]:
        if cls.disabled:
            return {}
        out = {}
        for name, metric in cls.timers.items():
            v = metric.compute()
            if v == v:
                out[name] = v
        return out

    @classmethod
    def percentiles(
        cls, qs: Sequence[float] = (50.0, 95.0)
    ) -> Dict[str, Dict[str, float]]:
        """Per-name duration percentiles over the raw-sample reservoir,
        e.g. ``{"Time/train_time": {"p50": 0.012, "p95": 0.034, "n": 128}}``.
        Empty when disabled or nothing has been timed since the last reset."""
        if cls.disabled:
            return {}
        import numpy as np

        out: Dict[str, Dict[str, float]] = {}
        for name, buf in cls.samples.items():
            if not buf:
                continue
            arr = np.fromiter(buf, dtype=np.float64)
            entry = {f"p{q:g}": float(np.percentile(arr, q)) for q in qs}
            entry["n"] = len(buf)
            out[name] = entry
        return out

    @classmethod
    def reset(cls) -> None:
        cls.timers = {}
        cls.samples = {}
