"""Device time per update step of the ops the scopes ``wm_encoder`` and
``wm_heads`` own: encoder, decoder, reward and continue heads and the
world-model loss, forward and backward."""

from chipbench import scope_reduce

NAME = "update_wm_encdec_ms"
UNIT = "ms"
LAYER = "L5 update"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
SCOPES = ("wm_encoder", "wm_heads")


def read(evidence):
    return scope_reduce.ms_per_step(evidence, SCOPES)
