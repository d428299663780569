"""Share of the traced window's device idle time that lies under none of the
program's ``Time/*`` spans (``span_reduce.idle_by_span``: each idle interval
cut at the span boundaries, each piece charged to the innermost span over it).
The seconds of idle per span, and each span's total and self time in the
window, go out on an earlier line."""

from chipbench import harness, span_reduce

NAME = "loop_idle_unspanned_pct"
UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "env_frames_per_s"


def read(evidence):
    if evidence.get("trace") is None:  # the run left no trace that reduces
        return None
    table = span_reduce.window_table()
    if table is None:
        return None
    idle = span_reduce.idle_by_span(table, table["window"])
    if idle is None:  # no device lane
        return None
    harness.note(idle_by_span_s=idle, spans_s=span_reduce.self_seconds(span_reduce.program_spans(table)))
    whole = sum(idle.values())
    return None if whole <= 0 else 100.0 * idle[span_reduce.UNSPANNED] / whole
