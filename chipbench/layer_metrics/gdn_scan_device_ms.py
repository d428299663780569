"""Device time per minibatch step of the gated delta rule alone in the three
linear layers: the scope ``gdn_scan`` (``ops/gated_delta_rule.py``'s chunked
form at chunks of 64, forward, backward and the blocks' rematerialised
forward)."""

from chipbench import qwen3_next_scopes

NAME = "gdn_scan_device_ms"
UNIT = "ms"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    return qwen3_next_scopes.ms_per_step(evidence, ("gdn_scan",))
