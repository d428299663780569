"""Device time per minibatch step of latent attention in all six blocks (the
trunk's five and the MTP module's): the scope ``mla_proj`` (the first norm,
the five products, the norms inside the latents, the rotary embedding, the
residual add) and, inside it, ``mla_kernel`` (the attention itself: the
block-sparse flash kernel under the causal mask), forward, backward and the
block's rematerialised forward."""

from chipbench import joyai_scopes

NAME = "mla_attn_device_ms"
UNIT = "ms"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
SCOPES = ("mla_proj", "mla_kernel")


def read(evidence):
    return joyai_scopes.ms_per_step(evidence, SCOPES)
