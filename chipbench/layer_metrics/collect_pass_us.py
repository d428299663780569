"""Device time of one cached forward pass of collection: the ops the scopes
``collect_denoise`` and ``collect_commit`` (block diffusion) or
``collect_decode`` (causal) own, over the cached passes the PROGRAM counted a
rollout (the ``passes`` counter of the ``jaxenv`` records of the window, the
prefill's one pass taken off; the driver holds the count to the shapes').  The
head is not in it: ``collect_score`` owns that."""

from chipbench import collect_scopes

NAME = "collect_pass_us"
UNIT = "us"
LAYER = "L3 collect"
SOURCE = "device_trace"
MOVES = "env_frames_per_s"


def read(evidence):
    seconds = collect_scopes.seconds_per_rollout(evidence, collect_scopes.PASSES)
    passes = evidence.get("collect", {}).get("cached_passes")
    return None if seconds is None or not passes else 1e6 * seconds / passes
