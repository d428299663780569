"""The program's own spans, from its telemetry and from the profiler's trace.

The loop's ``timer(name)`` regions (``sheeprl_tpu/utils/timer.py``) go two
ways: their sums into ``telemetry.jsonl`` (``timers_s``, once per log
interval) and, while a profiler runs, one ``TraceAnnotation`` each onto the
host lane of the trace, on the clock the device lanes use.  This module reads
both, for the ``loop_*`` readers beside ``layer_metrics/loop_env_pct.py``:

- ``timer_share``: a timer's share of the wall of the telemetry records, as
  ``loop_env_pct.shares`` defines that wall; None where the program has no
  such timer (the parent of the PR that added it).
- ``forest`` / ``self_seconds``: the ``Time/*`` spans of the traced window,
  nested by time on their one thread; a span's self time is its duration
  minus what its children cover.
- ``idle_by_span``: each idle interval of the first device (the window minus
  the union of its op intervals, as ``trace_reduce`` has it) cut at the span
  boundaries and charged to the innermost span that covers the piece;
  ``UNSPANNED`` takes what no span covers.

The trace is this run's: the newest ``*.xplane.pb`` under ``<OUT>/trace``,
written a moment ago by the one process a run is.  It is loaded once and kept,
not once per reader.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import harness, trace_reduce
from chipbench.layer_metrics import loop_env_pct

SPAN_PREFIX = "Time/"
UNSPANNED = "unspanned"
# the timers of dreamer_v3.main and sequence_batches that are nobody's child: together they cover
# what the loop times (Time/player_step, Time/env_step and the step's Time/replay_add lie inside
# the first)
ROOT_TIMERS = (
    "Time/env_interaction_time", "Time/feed_dispatch", "Time/train_time", "Time/params_refresh", "Time/loss_fetch",
    "Time/log",
)

Interval = Tuple[float, float]


# ------------------------------------------------------------- telemetry
def timer_share(evidence: dict, name: str) -> Optional[float]:
    """Per cent of the records' wall inside timer ``name``."""
    got = loop_env_pct.shares(evidence)
    if got is None or name not in got[0]:
        return None
    sums, wall = got
    return 100.0 * sums[name] / wall


def uncovered_share(evidence: dict) -> Optional[float]:
    """Per cent of the records' wall under no timer at all: 100 minus
    ``ROOT_TIMERS``.  The episode-end ring write, a ``Time/replay_add`` outside
    the step, cannot be told from the step's own in a sum: it is left on the
    uncovered side.  None for a program from before the spans."""
    got = loop_env_pct.shares(evidence)
    if got is None or "Time/params_refresh" not in got[0]:
        return None
    sums, wall = got
    return 100.0 * (1.0 - sum(sums.get(k, 0.0) for k in ROOT_TIMERS) / wall)


# ----------------------------------------------------------------- trace
@functools.lru_cache(maxsize=1)
def window_table() -> Optional[dict]:
    """This run's event table, clipped to the benchmark's window; None when
    the run left no trace."""
    try:
        table = trace_reduce.load_xplane(trace_reduce.newest_xplane(os.path.join(harness.OUT, "trace")))
    except FileNotFoundError:
        return None
    spans = [(s, s + d) for name, s, d in table["host"] if name == trace_reduce.WINDOW_SPAN]
    if not spans:
        return None
    t0, t1 = max(spans, key=lambda ab: ab[1] - ab[0])
    return dict(trace_reduce.clip_table(table, t0, t1), window=(t0, t1))


def program_spans(table: dict) -> List[list]:
    return [ev for ev in table["host"] if ev[0].startswith(SPAN_PREFIX)]


def forest(spans: Sequence[Sequence]) -> List[dict]:
    """``[name, start, dur]`` events of one thread -> roots of the nesting,
    each ``{"name", "start", "end", "children"}``."""
    roots: List[dict] = []
    stack: List[dict] = []
    for name, start, dur in sorted(spans, key=lambda ev: (ev[1], -ev[2])):
        node = {"name": name, "start": start, "end": start + dur, "children": []}
        while stack and stack[-1]["end"] <= start:
            stack.pop()
        (stack[-1]["children"] if stack else roots).append(node)
        stack.append(node)
    return roots


def _walk(nodes: Sequence[dict]):
    for node in nodes:
        yield node
        yield from _walk(node["children"])


def self_intervals(node: dict) -> List[Interval]:
    cover = trace_reduce.union((c["start"], min(c["end"], node["end"])) for c in node["children"])
    return trace_reduce.subtract([(node["start"], node["end"])], cover)


def self_seconds(spans: Sequence[Sequence]) -> Dict[str, Dict[str, float]]:
    """{name: {"seconds", "self_seconds", "count"}} over the spans given."""
    out: Dict[str, Dict[str, float]] = {}
    for node in _walk(forest(spans)):
        entry = out.setdefault(node["name"], {"seconds": 0.0, "self_seconds": 0.0, "count": 0})
        entry["seconds"] += (node["end"] - node["start"]) * 1e-9
        entry["self_seconds"] += trace_reduce.total(self_intervals(node)) * 1e-9
        entry["count"] += 1
    return out


def idle_by_span(table: dict, window: Interval) -> Optional[Dict[str, float]]:
    """Seconds of device idle time per innermost covering span (and
    ``UNSPANNED``); None without a device lane."""
    if not table["devices"]:
        return None
    lanes = table["devices"][sorted(table["devices"])[0]]
    busy = trace_reduce.union((s, s + d) for _, s, d in (lanes["ops"] or lanes["modules"]))
    idle = trace_reduce.subtract([window], busy)
    idle_ns = trace_reduce.total(idle)
    out: Dict[str, float] = {}
    roots = forest(program_spans(table))
    for node in _walk(roots):
        under = idle_ns - trace_reduce.total(trace_reduce.subtract(idle, self_intervals(node)))
        out[node["name"]] = out.get(node["name"], 0.0) + under * 1e-9
    spanned = trace_reduce.union((n["start"], n["end"]) for n in roots)
    out[UNSPANNED] = trace_reduce.total(trace_reduce.subtract(idle, spanned)) * 1e-9
    return out
