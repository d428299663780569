"""Device time per minibatch step of the attention op alone in the full layer:
the scope ``gated_attn_kernel`` (the block-sparse flash kernel under the causal
mask with 16 query heads sharing 2 key-value heads of 256, forward, backward and
the block's rematerialised forward; projections, norms, the partial rotary and
the output gate lie outside it, under ``gated_attn_proj``)."""

from chipbench import qwen3_next_scopes

NAME = "gated_attn_device_ms"
UNIT = "ms"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    return qwen3_next_scopes.ms_per_step(evidence, ("gated_attn_kernel",))
