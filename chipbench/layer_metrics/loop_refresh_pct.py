"""Share of the loop's wall inside ``Time/params_refresh``: the player's weight
refresh (``transfer_tree``: concatenate on the chip, one device-to-host copy),
which also waits for the update that produced the weights and the feed before it."""

from chipbench import span_reduce

NAME = "loop_refresh_pct"
UNIT = "%"
LAYER = "L2 loop"
SOURCE = "program_span"
MOVES = "env_frames_per_s"
TIMER = "Time/params_refresh"


def read(evidence):
    return span_reduce.timer_share(evidence, TIMER)
