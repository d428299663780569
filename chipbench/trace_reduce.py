"""The one reduction from a profiler trace to numbers.

``load_xplane`` turns a ``*.xplane.pb`` (read with ``jax.profiler.ProfileData``,
nothing but JAX) into a plain event table; ``reduce_events`` turns an event
table into the summary every trace-sourced per-layer metric reads.  The event
table is plain JSON, so a slice of a real trace can be kept under
``chipbench/testdata/`` and reduced again by the selftest.

Event table::

    {"devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "async": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

What a v5e trace holds (read by hand, PR 22): plane ``/device:TPU:n`` with the
lines ``XLA Modules`` (one event per executed program, named
``jit_train(<fingerprint>)``), ``XLA Ops`` (one event per executed HLO op, its
name the whole instruction text ``%fusion.7 = bf16[..] fusion(..)``; a
``while`` encloses the ops of its body in time), ``Async XLA Ops`` (one event
from each ``-start`` to its ``-done``: copies, slices and, on a mesh, the
collectives in flight) and ``Steps``; plane ``/host:CPU`` with one line per
thread, the ``python`` line holding the ``TraceAnnotation`` spans.  Host and
device share one clock.  Op names are cut down to ``short_name`` on loading.
Host lanes are never summed as device time: a trace with no device plane
raises.

Definitions (all clipped to the window):

- busy: the union of the op intervals of one device (overlapping and nested
  events count once); ``busy_s`` is its mean over the devices.
- idle share: 1 - busy / window.
- self time of an op: its duration minus the part its nested children cover.
- collective time is the union of the collectives' intervals, in flight
  (``Async XLA Ops``) or synchronous (``XLA Ops``); a collective is exposed
  while no op that is neither a collective nor an enclosing container runs
  on that device.
- an idle gap is labelled by the host span that overlaps it most (the
  shortest such span on a tie, so the innermost); the window span itself and
  spans shorter than a tenth of the gap do not label; no span -> "unlabelled".
  Gaps under 20 us are summed under one name and not attributed.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "chipbench:window"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_RE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|collective-broadcast)")
CONTAINER_RE = re.compile(r"^(while|conditional|call)\b")
SHORT_GAP_NS = 20_000.0  # gaps under 20 us are the device's own op-to-op latency
SHORT_GAP = "between-ops-under-20us"
_HOST_NOISE = ("ThreadpoolListener", "$", "SlinkyThreadPool", "PythonRefManager")

Interval = Tuple[float, float]
_INSTRUCTION_RE = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")
_OPERAND_RE = re.compile(r"%((?:params|opt_states)__[\w.]+)")


def short_name(text: str) -> str:
    """``%fusion.7 = bf16[8,4]{..} fusion(.. %params__a____b__.1 ..)`` ->
    ``fusion.7 bf16[8,4] <- params.a.b``: the instruction's own name first
    (so ``while``, ``all-reduce`` are recognised at the start), then its
    result type and the first parameter it reads, as a hint for a reader."""
    m = _INSTRUCTION_RE.match(text)
    if not m:
        return text[:120]
    out = m.group(1) + (" " + m.group(2) if m.group(2) else "")
    operand = _OPERAND_RE.search(text)
    if operand:
        hint = re.sub(r"_{2,}", ".", operand.group(1)).strip("._")
        out += " <- " + hint[:70]
    return out


# ------------------------------------------------------------------ loading
def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def is_device_plane(name: str) -> bool:
    low = name.lower()
    return low.startswith("/device:") and "cpu" not in low and "host" not in low


def load_xplane(path: str, min_host_ns: float = 20_000.0) -> dict:
    """xplane -> event table.  Host events shorter than ``min_host_ns`` are
    dropped unless they are the benchmark's own spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in data.planes:
        if is_device_plane(plane.name):
            lines = {line.name: line for line in plane.lines}

            def lane(line_name, rename=lambda n: n):
                line = lines.get(line_name)
                return [[rename(e.name), float(e.start_ns), float(e.duration_ns)] for e in (line.events if line else [])]

            ops, asyncs, mods = lane(OPS_LINE, short_name), lane(ASYNC_LINE, short_name), lane(MODULES_LINE)
            if ops or mods:
                devices[plane.name] = {"ops": ops, "async": asyncs, "modules": mods}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(_HOST_NOISE):
                        continue
                    if e.duration_ns >= min_host_ns or name.startswith("chipbench:"):
                        host.append([name, float(e.start_ns), float(e.duration_ns)])
    return {"devices": devices, "host": host}


def skeleton(path: str, per_line: int = 8) -> dict:
    """What a trace holds, for reading by hand: planes, lines, event counts
    and the first few events of each line with their stats."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = {
                "n": len(events),
                "first": [
                    {
                        "name": e.name[:120],
                        "start_ns": e.start_ns,
                        "dur_ns": e.duration_ns,
                        "stats": {str(k): str(v)[:80] for k, v in list(e.stats)[:12]},
                    }
                    for e in events[:per_line]
                ],
            }
        out[plane.name] = lines
    return out


def save_events(table: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(table, f, separators=(",", ":"))


def load_events(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def clip_table(table: dict, t0: float, t1: float) -> dict:
    """The part of an event table inside [t0, t1] (events clipped)."""

    def clip(events):
        out = []
        for name, s, d in events:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                out.append([name, a, b - a])
        return out

    return {
        "devices": {
            k: {"ops": clip(v["ops"]), "async": clip(v.get("async", [])), "modules": clip(v["modules"])}
            for k, v in table["devices"].items()
        },
        "host": clip(table["host"]),
    }


# ----------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Sequence[Interval], cover: Sequence[Interval]) -> List[Interval]:
    """``intervals`` (a union) minus ``cover`` (a union)."""
    out: List[Interval] = []
    j = 0
    for a, b in intervals:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def self_times(events: Sequence[Sequence]) -> List[Tuple[str, float, bool]]:
    """(name, self_ns, has_children) per event of ONE lane, by nesting."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [float(events[i][2]) for i in range(len(events))]
    parent_of = [False] * len(events)
    stack: List[int] = []
    for i in order:
        s, d = events[i][1], events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            p_end = events[p][1] + events[p][2]
            own[p] -= max(0.0, min(s + d, p_end) - s)
            parent_of[p] = True
        stack.append(i)
    return [(events[i][0], max(own[i], 0.0), parent_of[i]) for i in range(len(events))]


# ----------------------------------------------------------------- reduction
def _window(table: dict) -> Interval:
    spans = [(s, s + d) for name, s, d in table["host"] if name == WINDOW_SPAN]
    dev = [(s, s + d) for v in table["devices"].values() for _, s, d in (v["ops"] or v["modules"])]
    if not dev:
        raise RuntimeError("no device events in the trace: refusing to reduce host lanes as device time")
    if spans:  # host and device share one clock (checked on a v5e trace)
        return max(spans, key=lambda ab: ab[1] - ab[0])
    return min(a for a, _ in dev), max(b for _, b in dev)


def _top(pairs: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(pairs.items(), key=lambda kv: -kv[1])[:n]]


def reduce_events(table: dict, window: Optional[Interval] = None) -> dict:
    """Event table -> summary (seconds, unrounded)."""
    if not table["devices"]:
        raise RuntimeError("no device plane in the trace: refusing to reduce host lanes as device time")
    t0, t1 = window if window is not None else _window(table)
    table = clip_table(table, t0, t1)
    window_ns = t1 - t0
    ns = 1e-9

    per_device = {}
    programs: Dict[str, List[float]] = {}
    program_ops: Dict[str, Dict[str, float]] = {}
    ops_self: Dict[str, float] = {}
    gaps_labelled: Dict[str, float] = {}
    host = [(name, s, s + d) for name, s, d in table["host"] if name != WINDOW_SPAN]
    n_dev = len(table["devices"])
    for dev_name in sorted(table["devices"]):
        lanes = table["devices"][dev_name]
        ops, mods = lanes["ops"], lanes["modules"]
        busy = union((s, s + d) for _, s, d in (ops or mods))
        selfs = self_times(ops)
        leaf = [ev for ev, (_, _, parent) in zip(ops, selfs) if not parent and not CONTAINER_RE.match(ev[0])]
        coll = union(
            [(s, s + d) for name, s, d in leaf if COLLECTIVE_RE.match(name)]
            + [(s, s + d) for name, s, d in lanes.get("async", []) if COLLECTIVE_RE.match(name)]
        )
        other = union((s, s + d) for name, s, d in leaf if not COLLECTIVE_RE.match(name))
        whiles = union((s, s + d) for name, s, d in ops if name.startswith("while"))
        mod_iv = sorted((s, s + d, name) for name, s, d in mods)
        mod_starts = [m[0] for m in mod_iv]
        per_device[dev_name] = {
            "busy_s": total(busy) * ns,
            "idle_share": 1.0 - total(busy) / window_ns,
            "collective_s": total(coll) * ns,
            "collective_exposed_s": total(subtract(coll, other)) * ns,
            "while_s": total(whiles) * ns,
            "n_ops": len(ops),
        }
        for name, s, d in mods:
            entry = programs.setdefault(name, [0.0, 0.0, 0.0, 0.0])
            entry[0] += d * ns / n_dev
            entry[1] += 1.0 / n_dev
        # op self time and while time, charged to the program that encloses them
        for (name, s, d), (_, own, _) in zip(ops, selfs):
            ops_self[name] = ops_self.get(name, 0.0) + own * ns / n_dev
            mid = s + d / 2
            at = bisect.bisect_right(mod_starts, mid) - 1
            if at < 0 or mid >= mod_iv[at][1]:
                continue
            prog = mod_iv[at][2]
            if name.startswith("while"):
                programs[prog][2] += d * ns / n_dev
            if COLLECTIVE_RE.match(name):
                programs[prog][3] += own * ns / n_dev
            per_op = program_ops.setdefault(prog, {})
            per_op[name] = per_op.get(name, 0.0) + own * ns / n_dev
        # idle gaps, first device only (one host drives them all alike)
        if dev_name == sorted(table["devices"])[0]:
            for a, b in subtract([(t0, t1)], busy):
                if b - a < SHORT_GAP_NS:
                    gaps_labelled[SHORT_GAP] = gaps_labelled.get(SHORT_GAP, 0.0) + (b - a) * ns
                    continue
                best, best_overlap, best_len = "unlabelled", 0.1 * (b - a), float("inf")
                for name, hs, he in host:
                    overlap = min(b, he) - max(a, hs)
                    if overlap > best_overlap or (overlap == best_overlap and overlap > 0 and he - hs < best_len):
                        best, best_overlap, best_len = name, overlap, he - hs
                gaps_labelled[best] = gaps_labelled.get(best, 0.0) + (b - a) * ns

    busy_mean = sum(d["busy_s"] for d in per_device.values()) / n_dev
    return {
        "window_s": window_ns * ns,
        "busy_s": busy_mean,
        "idle_share": 1.0 - busy_mean / (window_ns * ns),
        "idle_share_worst": max(d["idle_share"] for d in per_device.values()),
        "collective_s": sum(d["collective_s"] for d in per_device.values()) / n_dev,
        "collective_exposed_s": sum(d["collective_exposed_s"] for d in per_device.values()) / n_dev,
        "per_device": per_device,
        # program -> seconds (mean over devices), executions per device, seconds
        # inside while loops, collective self seconds
        "programs": {
            k: {"seconds": v[0], "count": v[1], "while_s": v[2], "collective_s": v[3]} for k, v in programs.items()
        },
        "program_top_ops": {k: _top(v, 5) for k, v in program_ops.items()},
        "device_ops": _top(ops_self),
        "idle_gaps": _top(gaps_labelled),
    }


def program_matching(summary: dict, pattern: str) -> Optional[dict]:
    """The entry of ``summary["programs"]`` whose name matches ``pattern``
    (a regular expression) and that took most time; None if none does."""
    rx = re.compile(pattern)
    hits = [(v["seconds"], k, v) for k, v in summary["programs"].items() if rx.search(k)]
    if not hits:
        return None
    _, name, entry = max(hits)
    return dict(entry, name=name)


def reduce_dir(trace_dir: str) -> Tuple[dict, dict]:
    table = load_xplane(newest_xplane(trace_dir))
    return reduce_events(table), table
