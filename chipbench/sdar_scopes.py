"""Device time of the language-model policy's update, split by the
``jax.named_scope`` that owns each op (``make_episode_update_fn`` and
``models/sdar_moe.py`` wrap their phases in the scopes of ``TOKENS``).

The trace is read by ``scope_reduce.load_scoped``; the reduction is this
module's own (``by_scope``), because ``scope_reduce.by_scope`` knows
DreamerV3's tokens only.  Owner of an op: the last token on its own path; an
op without a token (the ``while`` of the minibatch scan, ops XLA made itself)
takes the owner of the nearest op that encloses it in time, else ``UNSCOPED``.
One kind of op is owned by its name: the TPU compiler rewrites
``jax.lax.ragged_dot`` into ops named ``ragged-dot-*`` whose path is that name
and no more (read in the compiled text, PR 26), and those are the grouped
products of ``moe_experts`` wherever they run.  Time is self time, so nothing
counts twice.  Reduced once a run and kept; the
milliseconds per scope and the share under no scope go out on an earlier
line."""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Dict, Optional, Sequence, Tuple

from chipbench import harness, scope_reduce, span_reduce, trace_reduce
from chipbench.peaks import peaks_for
from chipbench.scope_reduce import UNSCOPED

OWNED_BY_NAME = (("ragged-dot", "moe_experts"),)  # (how an op's name starts, its owner)
TOKENS = ("sdar_embed", "sdar_attn", "blockdiff_attn", "moe_router", "moe_dispatch", "moe_experts", "sdar_head", "ppo_loss", "ppo_optim")


def by_scope(devices: Dict[str, dict], window: Tuple[float, float], pattern: str, tokens: Sequence[str]) -> Optional[dict]:
    """Self seconds per owner inside the executions of the program whose name
    matches ``pattern`` that lie whole inside ``window``, the mean over the
    devices: ``{"count", "seconds", "self_s": {owner: s}, "by_op": {(owner, op
    name): s}}``; None when no such execution is there."""
    rx, token_rx = re.compile(pattern), re.compile(r"\b(" + "|".join(tokens) + r")\b")
    t0, t1 = window
    out = {"count": 0.0, "seconds": 0.0, "self_s": {}, "by_op": {}}
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
        stack = []  # (end, owner) of the ops that enclose the one at hand
        for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):  # a parent before its children
            name, start, dur, path = ops[i]
            while stack and stack[-1][0] <= start:
                stack.pop()
            hits = token_rx.findall(path or "")
            named = [owner for start_of, owner in OWNED_BY_NAME if name.startswith(start_of)]
            who = hits[-1] if hits else named[0] if named else (stack[-1][1] if stack else None)
            stack.append((start + dur, who))
            key, own = who or UNSCOPED, selfs[i][1] * 1e-9
            out["self_s"][key] = out["self_s"].get(key, 0.0) + own
            out["by_op"][(key, name)] = out["by_op"].get((key, name), 0.0) + own
    if not n_dev:
        return None
    for key in ("count", "seconds"):
        out[key] /= n_dev
    for key in ("self_s", "by_op"):
        out[key] = {k: v / n_dev for k, v in out[key].items()}
    return out


@functools.lru_cache(maxsize=1)
def _this_run(pattern: str) -> Optional[dict]:
    table = span_reduce.window_table()
    if table is None:
        return None
    devices = scope_reduce.load_scoped(trace_reduce.newest_xplane(os.path.join(harness.OUT, "trace")))
    got = by_scope(devices, table["window"], pattern, TOKENS)
    if got is None or not any(token in got["self_s"] for token in TOKENS):
        return None  # a program without the scopes: nothing to read
    per_call = 1e3 / got["count"]
    harness.note(sdar_update_scopes={
        "calls": got["count"],
        "ms_per_call": {k: v * per_call for k, v in got["self_s"].items()},
        "unscoped_pct": 100.0 * got["self_s"].get(UNSCOPED, 0.0) / got["seconds"],
        "top_ops_ms_per_call": {k: [[name, s * per_call] for name, s in ops]
                                for k, ops in scope_reduce.top_ops(got, 6).items()},
    })
    return got


def update_split(evidence: dict) -> Optional[dict]:
    pattern = evidence.get("programs", {}).get("update")
    if evidence.get("trace") is None or not pattern:
        return None
    return _this_run(pattern)


def seconds_per_step(evidence: dict, tokens: Sequence[str]) -> Optional[float]:
    """Self seconds of the ops the tokens own, per minibatch step."""
    got = update_split(evidence)
    steps_per_call = evidence.get("steps_per_call")
    if got is None or not steps_per_call:
        return None
    return sum(got["self_s"].get(t, 0.0) for t in tokens) / (got["count"] * steps_per_call)


def ms_per_step(evidence: dict, tokens: Sequence[str]) -> Optional[float]:
    seconds = seconds_per_step(evidence, tokens)
    return None if seconds is None else 1e3 * seconds


def roofline_pct(evidence: dict, flops_per_step: Optional[float], tokens: Sequence[str]) -> Optional[float]:
    """``flops_per_step`` over the tokens' device time, against the bf16 peak of ``peaks.json``."""
    seconds = seconds_per_step(evidence, tokens)
    if flops_per_step is None or not seconds:
        return None
    return 100.0 * flops_per_step / seconds / peaks_for(evidence["device_kind"])["bf16_flops_per_s"]
