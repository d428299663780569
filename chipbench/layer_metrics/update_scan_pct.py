"""Share of the update program's device time spent inside its ``while``
loops (the dynamic scan forward and backward, and imagination).  The default
path runs no custom kernel, so there is no kernel roofline to report yet."""

from chipbench.layer_metrics.update_device_ms import update_program

NAME = "update_scan_pct"
UNIT = "%"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    prog = update_program(evidence)
    return None if prog is None or prog["seconds"] <= 0 else 100.0 * prog["while_s"] / prog["seconds"]
