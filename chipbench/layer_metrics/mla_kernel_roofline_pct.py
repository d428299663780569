"""The causal attention kernel's share of its roofline: the FLOPs the (query,
key) pairs under the causal mask need in the UNABSORBED form, forward and
backward (``flops_joyai``: 3 x 2 x heads x (192 + 128) a pair at the published
sizes, the pairs counted from the mask and not from the tiles visited; lane
padding inside the kernel is not needed work), over the device time under the
scope ``mla_kernel`` (the attention op alone in all six blocks: forward,
backward and the block's rematerialised forward; projections, norms and the
rotary embedding lie outside it), against the bf16 peak of ``peaks.json``.
Compute-bound: about 500 FLOP a byte with keys and values re-read once per
query tile of 512, against a ridge of 240."""

from chipbench import joyai_scopes

NAME = "mla_kernel_roofline_pct"
UNIT = "%"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    return joyai_scopes.roofline_pct(evidence, evidence.get("mla_kernel_flops_per_step"), ("mla_kernel",))
