"""The masked attention's share of its roofline: the FLOPs the (query, visible
key) pairs of the block-diffusion mask need, forward and backward
(``flops_sdar``: 3 x 4 x heads x head size a pair, the pairs counted from the
mask rule and not from the tiles visited), over the device time under the
scope ``blockdiff_attn`` (the attention op alone: forward, backward and the
layer's rematerialised forward; projections, norms and rotary embedding lie
outside it), against the bf16 peak of ``peaks.json``.  Compute-bound: a tile
of 512 x 512 scores takes 67 MFLOP over 0.4 MB of keys and values."""

from chipbench import sdar_scopes

NAME = "blockdiff_attn_roofline_pct"
UNIT = "%"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    return sdar_scopes.roofline_pct(evidence, evidence.get("attention_flops_per_step"), ("blockdiff_attn",))
