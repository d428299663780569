"""Share of the loop's wall inside ``Time/env_step``: ``envs.step`` alone (for
an async vector env, the round trip to its worker processes).  A child of
``Time/env_interaction_time``."""

from chipbench import span_reduce

NAME = "loop_env_step_pct"
UNIT = "%"
LAYER = "L3 collect"
SOURCE = "program_span"
MOVES = "env_frames_per_s"
TIMER = "Time/env_step"


def read(evidence):
    return span_reduce.timer_share(evidence, TIMER)
