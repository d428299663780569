"""Device time per minibatch step of the scope ``moe_dispatch``: the sort of the
assignments by expert, the gathers into and out of the sorted buffer and their
transposes. It is what droplessness costs: the buffer has a row for every
assignment a token could make to a held expert."""

from chipbench import sdar_scopes

NAME = "moe_dispatch_ms"
UNIT = "ms"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
SCOPES = ("moe_dispatch",)


def read(evidence):
    return sdar_scopes.ms_per_step(evidence, SCOPES)
