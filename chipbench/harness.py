"""What every driver and ``run.py`` share: the run's context, the earlier
lines, the profiler switch and the checks' vocabulary.  No cell, config or
metric is named in this file."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")


class Incorrect(Exception):
    """A check on the program's outputs failed: the run reports
    ``"correct": false`` with this message on an earlier line."""


def require(cond: Any, what: str) -> None:
    if not cond:
        raise Incorrect(what)


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    with open(path) as f:
        return json.load(f)


def note(**line: Any) -> None:
    """An earlier line of standard output (never the last)."""
    print(json.dumps(line, default=str), flush=True)


@dataclass
class Context:
    name: str
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    tiny: bool
    t_process_start: float
    trace_dir: str = ""
    run_dir: str = ""
    evidence: Dict[str, Any] = field(default_factory=dict)

    def lap(self, phase: str) -> None:
        """Seconds since the process started, at the end of a set-up phase
        (printed on an earlier line: where set-up goes)."""
        self.evidence.setdefault("setup_laps_s", {})[phase] = time.perf_counter() - self.t_process_start

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def window_seconds(self) -> float:
        """A traced run profiles a short steady slice, not the whole window."""
        if self.trace:
            return min(self.seconds, float(self.traffic.get("trace_seconds", 4.0)))
        return self.seconds

    def overrides(self) -> List[str]:
        """The override list handed to the program: the configuration's, the
        traffic mix's, the seed, and where this run's files go."""
        ov = list(self.config["overrides"]) + list(self.traffic.get("overrides", []))
        if self.tiny:
            ov += list(self.config["tiny_overrides"]) + list(self.traffic.get("tiny_overrides", []))
        return ov + [f"seed={self.seed}", f"root_dir={self.run_dir}", f"run_name={self.name}"]

    def param(self, key: str, default: Any = None) -> Any:
        if self.tiny and key in self.traffic.get("tiny", {}):
            return self.traffic["tiny"][key]
        return self.traffic.get(key, default)

    @contextlib.contextmanager
    def profile(self):
        """The steady slice: under ``--trace 1`` the profiler runs around it
        (host TraceMe spans on, the Python tracer off: it would slow the host
        the loop cell measures), with the window span on the host lane."""
        import jax

        if not self.trace:
            yield
            return
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("chipbench:window"):
                yield
        finally:
            jax.profiler.stop_trace()


def span(name: str):
    """A host span of the benchmark's own around a call into a layer."""
    import jax

    return jax.profiler.TraceAnnotation("chipbench:" + name)


def device_report(chips: int) -> dict:
    import jax

    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max((s.get("peak_bytes_in_use", 0) for s in stats), default=0),
    }


def fetch_losses(metrics_list) -> Dict[str, Any]:
    """Per-step dicts of device scalars -> {name: np.ndarray over steps}."""
    import jax
    import numpy as np

    host = jax.device_get(list(metrics_list))
    return {k: np.asarray([m[k] for m in host]) for k in host[0]}


def spread_over(name: str, array, devices) -> None:
    """``array`` must hold a shard on every one of ``devices``."""
    held = {s.device for s in array.addressable_shards}
    missing = [str(d) for d in devices if d not in held]
    require(not missing, f"{name} holds no shard on {missing}")
