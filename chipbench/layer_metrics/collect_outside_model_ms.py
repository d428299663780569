"""The part of collection that is not the model, per iteration: the ops the
scopes ``collect_sample`` (the position's and the token's choice, the recorded
log-probability and value) and ``collect_env`` (``vector_step``) own."""

from chipbench import collect_scopes

NAME = "collect_outside_model_ms"
UNIT = "ms"
LAYER = "L3 collect"
SOURCE = "device_trace"
MOVES = "env_frames_per_s"


def read(evidence):
    seconds = collect_scopes.seconds_per_rollout(evidence, collect_scopes.OUTSIDE_MODEL)
    return None if seconds is None else 1e3 * seconds
