"""Share of the loop's wall inside ``Time/player_step``: observation
preparation, the player's policy program and the fetch of its actions, where
the host waits for it (telemetry ``timers_s`` over the wall of the same
records, as ``loop_env_pct.shares``).  A child of ``Time/env_interaction_time``."""

from chipbench import span_reduce

NAME = "loop_player_step_pct"
UNIT = "%"
LAYER = "L3 collect"
SOURCE = "program_span"
MOVES = "env_frames_per_s"
TIMER = "Time/player_step"


def read(evidence):
    return span_reduce.timer_share(evidence, TIMER)
