"""Share of the loop's wall under neither of the program's two timers: the
replay feed dispatch, the player's weight refresh, the loss fetch, logging."""

from chipbench.layer_metrics import loop_env_pct

NAME = "loop_unattributed_pct"
UNIT = "%"
LAYER = "L2 loop"
SOURCE = "program_span"
MOVES = "env_frames_per_s"


def read(evidence):
    got = loop_env_pct.shares(evidence)
    if got is None:
        return None
    sums, wall = got
    return 100.0 * (1.0 - (sums.get("Time/env_interaction_time", 0.0) + sums.get("Time/train_time", 0.0)) / wall)
