from sheeprl_tpu.ops.ring_attention import (  # noqa: F401
    blockwise_attention,
    make_ring_attention,
    ring_attention,
)
from sheeprl_tpu.ops.pallas_gru import fused_gru_cell, reference_gru_cell  # noqa: F401
