"""Device time per update step of the ops the scopes ``bh_actor`` and
``bh_critic`` own: the heads over the imagined trajectory, lambda values,
moments, and the two losses with their backward passes."""

from chipbench import scope_reduce

NAME = "update_actor_critic_ms"
UNIT = "ms"
LAYER = "L5 update"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"
SCOPES = ("bh_actor", "bh_critic")


def read(evidence):
    return scope_reduce.ms_per_step(evidence, SCOPES)
