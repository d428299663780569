"""Device time of the language-model policies' rollout program, split by the
phase of collection that owns each op (``envs/jax/collect.py`` wraps the
phases in the ``jax.named_scope``s of ``TOKENS``; the model's own scopes lie
beneath them and are not told apart here).

The trace is read by ``scope_reduce.load_scoped``; the reduction is this
module's own.  Owner of an op: the collect token on its own path; an op
without one takes the owner of the nearest op that encloses it in time.  What
is left are ops inside the scan's ``while`` (which has no path itself and stays
without an owner, as every pathless op at the program's top level does) that
the compiler made or renamed and that carry no scope on their path: the grouped
products (``ragged-dot-*``), copies, slices, converts.  The program runs one
phase after the other on one device, so such an op goes to the phase of the
last owned op before it inside the same container (``filled_s``, by owner).
How far that rule can be wrong is read off the same trace: a filled op that the
NEXT owned op of its container brackets with the same owner lies inside that
phase's run of ops; one that lies between two phases could be either's, and
its time is ``boundary_s``.  Where more than ``BOUNDARY_MAX`` of the rollout's
device time lies so, the split is not reported (``seconds_per_rollout`` gives
None).  Time is self time, so nothing counts twice.  Reduced once a run and
kept."""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Dict, Optional, Sequence, Tuple

from chipbench import harness, scope_reduce, span_reduce, trace_reduce
from chipbench.scope_reduce import UNSCOPED

TOKENS = ("collect_prefill", "collect_denoise", "collect_decode", "collect_commit", "collect_score", "collect_sample",
          "collect_env")
PASSES = ("collect_denoise", "collect_commit", "collect_decode")  # the cached passes of the model
MODEL = PASSES + ("collect_score",)  # and its head
OUTSIDE_MODEL = ("collect_sample", "collect_env")
_TOKEN_RE = re.compile(r"\b(" + "|".join(TOKENS) + r")\b")
# the share of a rollout's device time that may lie between two phases, owned by the order of the ops alone,
# before the split stops being reported: 0.02 % on the chip (my chip runs, PR 32: the scan's body is one
# schedule, the same every pass), so this is fifty times the reading and far under what the readers resolve
BOUNDARY_MAX = 0.01


def by_scope(devices: Dict[str, dict], window: Tuple[float, float], pattern: str) -> Optional[dict]:
    """Self seconds per owner inside the executions of the program whose name
    matches ``pattern`` that lie whole inside ``window``, the mean over the
    devices: ``{"count", "seconds", "self_s": {owner: s}, "filled_s": {owner:
    s}, "boundary_s", "by_op": {(owner, op name): s}}``; None when no such
    execution is there."""
    rx = re.compile(pattern)
    t0, t1 = window
    out = {"count": 0.0, "seconds": 0.0, "boundary_s": 0.0, "self_s": {}, "filled_s": {}, "by_op": {}}
    n_dev = 0
    for dev in sorted(devices):
        runs = sorted((s, s + d) for name, s, d in devices[dev]["modules"] if rx.search(name) and s >= t0 and s + d <= t1)
        if not runs:
            continue
        n_dev += 1
        starts = [a for a, _ in runs]
        ops = [op for op in devices[dev]["ops"]
               if (at := bisect.bisect_right(starts, op[1]) - 1) >= 0 and op[1] + op[2] <= runs[at][1]]
        out["count"] += len(runs)
        out["seconds"] += sum(b - a for a, b in runs) * 1e-9
        selfs = trace_reduce.self_times([op[:3] for op in ops])
        # the ops that enclose the one at hand: [end, owner, owner of the last owned op inside it, seconds
        # filled in since that op and not bracketed yet]
        stack = [[float("inf"), None, None, 0.0]]
        for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):  # a parent before its children
            name, start, dur, path = ops[i]
            while stack[-1][0] <= start:
                left_open = stack.pop()[3]  # what a container leaves open waits for its parent's next owned op
                stack[-1][3] += left_open
            hit = _TOKEN_RE.search(path or "")
            who, filled = (hit.group(1) if hit else stack[-1][1]), False
            if who is None and len(stack) > 1 and stack[-1][2] is not None:  # never at the program's top level
                who, filled = stack[-1][2], True
            key, own = who or UNSCOPED, selfs[i][1] * 1e-9
            if filled:
                stack[-1][3] += own
                out["filled_s"][key] = out["filled_s"].get(key, 0.0) + own
            elif who is not None:
                if who != stack[-1][2]:  # another phase begins: what was filled in since the last one's op could be either's
                    out["boundary_s"] += stack[-1][3]
                stack[-1][2], stack[-1][3] = who, 0.0
            # an op that was filled in is no owner for what it encloses: its children are filled in their turn
            stack.append([start + dur, None if filled else who, who, 0.0])
            out["self_s"][key] = out["self_s"].get(key, 0.0) + own
            out["by_op"][(key, name)] = out["by_op"].get((key, name), 0.0) + own
        out["boundary_s"] += sum(entry[3] for entry in stack)  # filled in after the last owned op of all
    if not n_dev:
        return None
    for key in ("count", "seconds", "boundary_s"):
        out[key] /= n_dev
    for key in ("self_s", "filled_s", "by_op"):
        out[key] = {k: v / n_dev for k, v in out[key].items()}
    return out


@functools.lru_cache(maxsize=1)
def _this_run(pattern: str) -> Optional[dict]:
    table = span_reduce.window_table()
    if table is None:
        return None
    devices = scope_reduce.load_scoped(trace_reduce.newest_xplane(os.path.join(harness.OUT, "trace")))
    got = by_scope(devices, table["window"], pattern)
    if got is None:
        return None  # a program whose rollout has another name: nothing to read
    per_rollout = 1e3 / got["count"]
    harness.note(collect_scopes={
        "rollouts": got["count"], "ms_per_rollout": got["seconds"] * per_rollout,
        "ms_per_rollout_by_scope": {k: v * per_rollout for k, v in got["self_s"].items()},
        "unscoped_pct": 100.0 * got["self_s"].get(UNSCOPED, 0.0) / got["seconds"],
        # of each owner's own time, the share that found it by the fill rule; of the rollout, the share that
        # lies between two phases (the split is reported while that is under BOUNDARY_MAX)
        "filled_pct_by_scope": {k: 100.0 * v / got["self_s"][k] for k, v in got["filled_s"].items()},
        "filled_pct": 100.0 * sum(got["filled_s"].values()) / got["seconds"],
        "boundary_pct": 100.0 * got["boundary_s"] / got["seconds"], "boundary_max_pct": 100.0 * BOUNDARY_MAX,
        "top_ops_ms_per_rollout": {k: [[name, s * per_rollout] for name, s in ops]
                                   for k, ops in scope_reduce.top_ops(got, 6).items()},
    })
    return got


def rollout_split(evidence: dict) -> Optional[dict]:
    """``by_scope`` of this run's rollout program (the traffic mix's
    ``programs.collect``); None in a run without a trace or without the program."""
    pattern = evidence.get("programs", {}).get("collect")
    if evidence.get("trace") is None or not pattern:
        return None
    return _this_run(pattern)


def seconds_per_rollout(evidence: dict, tokens: Sequence[str]) -> Optional[float]:
    """Self seconds a rollout of the ops the tokens own; None for a program
    without the scopes, and where too much of the time lies between two phases."""
    got = rollout_split(evidence)
    if got is None or not any(token in got["self_s"] for token in TOKENS) or got["boundary_s"] > BOUNDARY_MAX * got["seconds"]:
        return None
    return sum(got["self_s"].get(t, 0.0) for t in tokens) / got["count"]
