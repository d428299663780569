"""Distinct held experts one cached pass of collection reaches in one routed
layer, the mean over the window's rollouts, as the PROGRAM counted them: the
window's delta of the ``experts_reached`` counter of the ``jaxenv`` records
over (rollouts x cached passes x routed trunk layers whose routed part runs;
``reach_collect.py``).  The expert weights a pass has to read follow it: 2 of
16 held where 4 rows choose 8 of 256 experts each.  None for a program without
the counter."""

from chipbench import reach_collect

NAME = "collect_experts_reached"
UNIT = "experts"
LAYER = "L3 collect"
SOURCE = "program_counter"
MOVES = "env_frames_per_s"


def read(evidence):
    got = reach_collect.counted(evidence)
    return None if got is None else got["mean_reached"]
