"""Share of the loop's wall inside ``Time/replay_add``: the host ring write and
the upload and ring write of the device cache, in the step (a child of
``Time/env_interaction_time``) and at an episode's end."""

from chipbench import span_reduce

NAME = "loop_replay_add_pct"
UNIT = "%"
LAYER = "L4 replay"
SOURCE = "program_span"
MOVES = "env_frames_per_s"
TIMER = "Time/replay_add"


def read(evidence):
    return span_reduce.timer_share(evidence, TIMER)
