"""Share of the loop's wall inside ``Time/loss_fetch``: the host fetch of the last
update's losses (``device_get_metrics`` under ``block_until_ready``)."""

from chipbench import span_reduce

NAME = "loop_fetch_pct"
UNIT = "%"
LAYER = "L2 loop"
SOURCE = "program_span"
MOVES = "env_frames_per_s"
TIMER = "Time/loss_fetch"


def read(evidence):
    return span_reduce.timer_share(evidence, TIMER)
