"""Device time per update step of the ops the scope ``wm_dynamics`` owns
(``scope_reduce``: self time by the ``jax.named_scope`` on each op's path):
the RSSM in the world-model loss, forward and backward — initial state, embed
projection, the dynamic scan (its ``while`` loops), prior logits."""

from chipbench import scope_reduce

NAME = "update_wm_dynamics_ms"
UNIT = "ms"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
SCOPES = ("wm_dynamics",)


def read(evidence):
    return scope_reduce.ms_per_step(evidence, SCOPES)
