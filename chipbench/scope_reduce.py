"""Device time of the update program, split by the ``jax.named_scope`` that
owns each op.

``make_train_fn`` (``sheeprl_tpu/algos/dreamer_v3/dreamer_v3.py``) wraps each
phase of the update in one scope; the nine names are ``TOKENS``.  On a v5e
trace (read by hand, PR 24) the path does not reach the event's name — the
instruction text is printed without its metadata — and
``jax.profiler.ProfileData`` shows only an event's own stats.  It is in the
plane's event metadata: every ``XLA Ops`` event points at an
``XEventMetadata`` whose stat ``tf_op`` holds the HLO ``op_name``, e.g.
``jit(train)/transpose(jvp(wm_dynamics))/RSSM._transition/.../dot_general:``.
So this module reads the ``*.xplane.pb`` itself, with the few lines of
protobuf wire format that takes (``load_scoped``; field numbers from
``tsl/profiler/protobuf/xplane.proto``), and keeps the path per op.

Owner of an op: the last token on its own path (scopes are disjoint in the
program, so there is one; were there two, the innermost wins).  A ``while``
has no path at all on that trace, and runs its body inside its own event: a
container without a path goes to whoever owns most of the self time nested in
it.  Any other op without a token takes the owner of the nearest op that
encloses it in time.  What still has no owner is ``UNSCOPED`` (the loss
metrics, the sentinel's checks, parameter relayouts, ops XLA made itself).
Time is self time (an event's duration minus the events nested in it), so a
``while`` keeps only its own overhead and nothing counts twice; ``while_s`` is
the whole duration of the outermost ``while`` events of a token.  A fusion that
XLA built from ops of two scopes carries one path, and goes to that one.

``update_split(evidence)`` is what the ``update_*`` readers call: this run's
trace (the newest under ``<OUT>/trace``, as ``span_reduce`` takes it), the
benchmark's window, the update program by the traffic mix's pattern; reduced
once and kept.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench import harness, span_reduce, trace_reduce

TOKENS = (
    "wm_encoder", "wm_dynamics", "wm_heads", "wm_optim", "bh_imagine", "bh_actor", "bh_critic", "actor_optim",
    "critic_optim",
)
UNSCOPED = "unscoped"
PATH_STAT = "tf_op"
_TOKEN_RE = re.compile(r"\b(" + "|".join(TOKENS) + r")\b")


def owner(path: str) -> Optional[str]:
    hits = _TOKEN_RE.findall(path or "")
    return hits[-1] if hits else None


# ------------------------------------------------ protobuf wire format
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, start: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the message in ``buf[start:end]``: an int for
    a varint, ``(a, b)`` offsets for a length-delimited field; fixed-width
    fields (the doubles of a stat) are skipped."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane file")


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span: Tuple[int, int]) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for num, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane(buf: bytes, span: Tuple[int, int]) -> Optional[dict]:
    """One device plane -> {"ops": [[name, start_ns, dur_ns, path]],
    "modules": [[name, start_ns, dur_ns]]}; None for any other plane."""
    name, lines, event_meta, stat_meta = "", [], [], {}
    for num, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            event_meta.append(v)
        elif num == 5:
            key, value = _map_entry(buf, v)
            for n2, v2 in _fields(buf, *value):
                if n2 == 2:
                    stat_meta[key] = _text(buf, v2)
    if not trace_reduce.is_device_plane(name):
        return None
    path_ids = {k for k, stat in stat_meta.items() if stat == PATH_STAT}
    meta: Dict[int, Tuple[str, str]] = {}
    for entry in event_meta:
        key, value = _map_entry(buf, entry)
        text, path = "", ""
        for num, v in _fields(buf, *value):
            if num == 2:
                text = _text(buf, v)
            elif num == 5:  # XStat: metadata_id 1, str_value 5, ref_value 7
                stat = dict(_fields(buf, *v))
                if stat.get(1) in path_ids:
                    path = _text(buf, stat[5]) if 5 in stat else stat_meta.get(stat.get(7), "")
        meta[key] = (text, path)
    out = {"ops": [], "modules": []}
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for num, v in _fields(buf, *line):
            if num == 2:
                line_name = _text(buf, v)
            elif num == 3:
                t0_ns = v
            elif num == 4:
                events.append(v)
        if line_name not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            continue
        for ev in events:
            f = dict(_fields(buf, *ev))  # XEvent: metadata_id 1, offset_ps 2, duration_ps 3
            text, path = meta.get(f.get(1, 0), ("", ""))
            start, dur = t0_ns + f.get(2, 0) / 1e3, f.get(3, 0) / 1e3
            if line_name == trace_reduce.OPS_LINE:
                out["ops"].append([trace_reduce.short_name(text), start, dur, path])
            else:
                out["modules"].append([text, start, dur])
    return (name, out) if out["ops"] or out["modules"] else None


def load_scoped(path: str) -> Dict[str, dict]:
    """``*.xplane.pb`` -> {device plane: {"ops": [[name, start_ns, dur_ns,
    path]], "modules": [[name, start_ns, dur_ns]]}}, on the clock
    ``trace_reduce.load_xplane`` gives."""
    with open(path, "rb") as f:
        buf = f.read()
    devices = {}
    for num, v in _fields(buf, 0, len(buf)):
        if num == 1:  # XSpace.planes
            got = _plane(buf, v)
            if got is not None:
                devices[got[0]] = got[1]
    return devices


# ---------------------------------------------------------------- reduction
def by_scope(devices: Dict[str, dict], window: Tuple[float, float], pattern: str) -> Optional[dict]:
    """Self seconds per owner inside the executions of the program whose name
    matches ``pattern`` that lie whole inside ``window``; the mean over the
    devices.  ``{"count", "seconds", "self_s": {owner: s}, "while_s": {owner:
    s}, "by_op": {(owner, op name): s}}``; None when no such execution is
    there."""
    rx = re.compile(pattern)
    t0, t1 = window
    n_dev = 0
    out = {"count": 0.0, "seconds": 0.0, "self_s": {}, "while_s": {}, "by_op": {}}
    for dev in sorted(devices):
        runs = sorted((s, s + d) for name, s, d in devices[dev]["modules"] if rx.search(name) and s >= t0 and s + d <= t1)
        if not runs:
            continue
        n_dev += 1
        starts = [a for a, _ in runs]
        ops = []
        for op in devices[dev]["ops"]:
            at = bisect.bisect_right(starts, op[1]) - 1
            if at >= 0 and op[1] + op[2] <= runs[at][1]:
                ops.append(op)
        out["count"] += len(runs)
        out["seconds"] += sum(b - a for a, b in runs) * 1e-9
        _charge(ops, out)
    if not n_dev:
        return None
    for key in ("count", "seconds"):
        out[key] /= n_dev
    for key in ("self_s", "while_s", "by_op"):
        out[key] = {k: v / n_dev for k, v in out[key].items()}
    return out


def top_ops(reduced: dict, n: int = 4) -> Dict[str, list]:
    """{owner: the ``n`` ops with most self time, [[name, seconds], ...]}."""
    per: Dict[str, list] = {}
    for (who, name), seconds in sorted(reduced["by_op"].items(), key=lambda kv: -kv[1]):
        if len(per.setdefault(who, [])) < n:
            per[who].append([name, seconds])
    return per


def _charge(ops: Sequence[Sequence], out: dict) -> None:
    """Find each op's owner by the rules above (ops of one device, nested by
    time) and add its self time there."""
    selfs = trace_reduce.self_times([op[:3] for op in ops])
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))  # a parent before its children
    parent = [-1] * len(ops)
    owners: List[Optional[str]] = [owner(op[3]) for op in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= ops[i][1]:
            stack.pop()
        parent[i] = stack[-1] if stack else -1
        stack.append(i)
    # a container with no path of its own (a ``while``): whoever owns most of the self time nested in it
    inside: Dict[int, Dict[str, float]] = {}
    for i in reversed(order):  # children before their parent
        nested = inside.pop(i, None)
        if owners[i] is None and nested:
            owners[i] = max(nested, key=nested.get)
        if parent[i] >= 0:
            up = inside.setdefault(parent[i], {})
            for who, seconds in (nested or {}).items():
                up[who] = up.get(who, 0.0) + seconds
            if owners[i] is not None:
                up[owners[i]] = up.get(owners[i], 0.0) + selfs[i][1]
    for i in order:
        if owners[i] is None and parent[i] >= 0:
            owners[i] = owners[parent[i]]
        name, _, dur, _ = ops[i]
        key = owners[i] or UNSCOPED
        own = selfs[i][1] * 1e-9
        out["self_s"][key] = out["self_s"].get(key, 0.0) + own
        out["by_op"][(key, name)] = out["by_op"].get((key, name), 0.0) + own
        if name.startswith("while") and not _inside_a_while(i, parent, ops):
            out["while_s"][key] = out["while_s"].get(key, 0.0) + dur * 1e-9


def _inside_a_while(i: int, parent: Sequence[int], ops: Sequence[Sequence]) -> bool:
    i = parent[i]
    while i >= 0:
        if ops[i][0].startswith("while"):
            return True
        i = parent[i]
    return False


# ------------------------------------------------------------- this run's
@functools.lru_cache(maxsize=1)
def _this_run(pattern: str) -> Optional[dict]:
    table = span_reduce.window_table()
    if table is None:
        return None
    devices = load_scoped(trace_reduce.newest_xplane(os.path.join(harness.OUT, "trace")))
    return by_scope(devices, table["window"], pattern)


def update_split(evidence: dict) -> Optional[dict]:
    """``by_scope`` of this run's update program; None in a run without a
    trace, without the program, or of a program without the scopes."""
    pattern = evidence.get("programs", {}).get("update")
    if evidence.get("trace") is None or not pattern:
        return None
    got = _this_run(pattern)
    if got is None or not any(token in got["self_s"] for token in TOKENS):
        return None
    return got


def ms_per_step(evidence: dict, tokens: Sequence[str]) -> Optional[float]:
    got = update_split(evidence)
    return None if got is None else 1e3 * sum(got["self_s"].get(t, 0.0) for t in tokens) / got["count"]
