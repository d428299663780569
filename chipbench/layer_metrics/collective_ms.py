"""Device time of all-reduce / all-gather / reduce-scatter ops per update
step (their self time in the trace, mean over the devices)."""

from chipbench.layer_metrics.update_device_ms import update_program

NAME = "collective_ms"
UNIT = "ms"
LAYER = "L1 runtime"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
KEY = "collective_s"


def read(evidence, key=KEY):
    prog = update_program(evidence)
    return None if prog is None else 1e3 * evidence["trace"][key] / prog["count"]
