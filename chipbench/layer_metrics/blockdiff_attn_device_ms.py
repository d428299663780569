"""Device time per minibatch step of the attention block: the scope
``sdar_attn`` (q, k, v and output projections, per-head norms, rotary
embedding) and, inside it, ``blockdiff_attn`` (the masked attention itself:
the block-sparse flash kernel), forward, backward and the layer's
rematerialised forward."""

from chipbench import sdar_scopes

NAME = "blockdiff_attn_device_ms"
UNIT = "ms"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
SCOPES = ("sdar_attn", "blockdiff_attn")


def read(evidence):
    return sdar_scopes.ms_per_step(evidence, SCOPES)
