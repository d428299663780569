"""Device time per update step of the ops the scope ``bh_imagine`` owns: the
imagination scan (its ``while`` loop) with its inputs and noise."""

from chipbench import scope_reduce

NAME = "update_imagine_ms"
UNIT = "ms"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
SCOPES = ("bh_imagine",)


def read(evidence):
    return scope_reduce.ms_per_step(evidence, SCOPES)
