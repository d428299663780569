"""Device time per minibatch step of the expert layer: the ops the scopes
``moe_router``, ``moe_dispatch`` and ``moe_experts`` own (forward, backward
and the layer's rematerialised forward)."""

from chipbench import sdar_scopes

NAME = "moe_device_ms"
UNIT = "ms"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
SCOPES = ("moe_router", "moe_dispatch", "moe_experts")


def read(evidence):
    return sdar_scopes.ms_per_step(evidence, SCOPES)
