"""Share of the loop's wall the host spends waiting for the update's new
parameters: ``ppo.main``'s ``Time/update_wait`` (a block on what the update
call returned, between the call's dispatch, ``Time/train_time``, and the
hand-over, ``Time/publish``), over the telemetry records of the steady part of
the run.  In a closed loop the host waits for the rollout or for the update
nearly all the time: this and ``collect_wait_pct`` then sum to the wall but
for the host's own work.  None for a program without the span (its wait for
the update lies in ``Time/publish``)."""

from chipbench import span_reduce

NAME = "update_wait_pct"
UNIT = "%"
LAYER = "L2 loop"
SOURCE = "program_span"
MOVES = "env_frames_per_s"


def read(evidence):
    return span_reduce.timer_share(evidence, "Time/update_wait")
