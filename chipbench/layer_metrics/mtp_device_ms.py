"""Device time per minibatch step of the multi-token-prediction module: every op
whose scope path holds ``mtp_module`` anywhere (the next tokens' embedding, the
two norms and the projection, the module's block of the routed kind, its norm
and head pass with the cross-entropy), forward, backward and the block's
rematerialised forward: what multi-token prediction costs, one number.  It
overlaps ``mla_attn_device_ms`` and the ``moe_*`` times by the module's own
block, whose latent attention and routed layer those count too; the grouped
products the compiler renames carry no path and are not in it."""

from chipbench import joyai_scopes

NAME = "mtp_device_ms"
UNIT = "ms"
LAYER = "L5 update"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    return joyai_scopes.ms_per_step(evidence, (joyai_scopes.MODULE,), under=True)
