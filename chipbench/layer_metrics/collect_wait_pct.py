"""Share of the loop's wall the host spends on collection: dispatching the
rollout (``Time/env_interaction_time``) and waiting for its episode events
(``Time/collect_wait``, where the host first waits for the rollout), over the
telemetry records of the steady part of the run.  None for a program without
the second span (its wait lies outside every span)."""

from chipbench import span_reduce

NAME = "collect_wait_pct"
UNIT = "%"
LAYER = "L3 collect"
SOURCE = "program_span"
MOVES = "env_frames_per_s"


def read(evidence):
    wait = span_reduce.timer_share(evidence, "Time/collect_wait")
    dispatch = span_reduce.timer_share(evidence, "Time/env_interaction_time")
    return None if wait is None or dispatch is None else wait + dispatch
