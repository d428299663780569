"""Share of the loop's wall under none of the program's timers: what is left
of 100 after ``Time/env_interaction_time`` (its three children and its self
time), ``Time/feed_dispatch``, ``Time/train_time``, ``Time/params_refresh``,
``Time/loss_fetch`` and ``Time/log``.  Episode bookkeeping, the ratio, the
sentinel's tick and the checkpoint check live here."""

from chipbench import span_reduce

NAME = "loop_uncovered_pct"
UNIT = "%"
LAYER = "L2 loop"
SOURCE = "program_span"
MOVES = "env_frames_per_s"


def read(evidence):
    return span_reduce.uncovered_share(evidence)
