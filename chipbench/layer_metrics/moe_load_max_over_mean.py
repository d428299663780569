"""Largest over mean of the assignments per held expert, in the worst layer:
the update's own counter (``MoE/load_max_over_mean``), the mean over the
window's update calls.  1 is even routing; a dropless layer computes every
assignment whatever this reads."""

NAME = "moe_load_max_over_mean"
UNIT = "ratio"
LAYER = "L5 update"
SOURCE = "program_counter"
MOVES = "train_frames_per_s"


def read(evidence):
    value = evidence.get("moe", {}).get("load_max_over_mean")
    return None if value is None else float(value)
