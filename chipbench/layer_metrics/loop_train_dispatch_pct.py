"""Share of the loop's wall inside the program's ``Time/train_time`` timer:
the dispatch of the gradient steps (it does not wait for the device)."""

from chipbench.layer_metrics import loop_env_pct

NAME = "loop_train_dispatch_pct"
UNIT = "%"
LAYER = "L2 loop"
SOURCE = "program_span"
MOVES = "env_frames_per_s"


def read(evidence):
    return loop_env_pct.read(evidence, timer="Time/train_time")
