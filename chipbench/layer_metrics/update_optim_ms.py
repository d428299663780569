"""Device time per update step of the ops the three ``*_optim`` scopes own:
gradient clip, optimizer update and ``apply_updates`` of the world model, the
actor and the critic."""

from chipbench import scope_reduce

NAME = "update_optim_ms"
UNIT = "ms"
LAYER = "L5 update"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
SCOPES = ("wm_optim", "actor_optim", "critic_optim")


def read(evidence):
    return scope_reduce.ms_per_step(evidence, SCOPES)
