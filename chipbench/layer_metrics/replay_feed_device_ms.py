"""Device time of the replay feed per gradient step: every program whose
name matches the traffic mix's ``programs.feed`` (the sampler's gather and
the slices that cut its result into batches), over the update's count."""

import re

from chipbench.layer_metrics.update_device_ms import update_program

NAME = "replay_feed_device_ms"
UNIT = "ms"
LAYER = "L4 replay"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    prog, pattern = update_program(evidence), evidence.get("programs", {}).get("feed")
    if prog is None or not pattern:
        return None
    rx = re.compile(pattern)
    seconds = sum(v["seconds"] for k, v in evidence["trace"]["programs"].items() if rx.search(k))
    return 1e3 * seconds / prog["count"]
