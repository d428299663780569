"""Device time per minibatch step of the three Gated DeltaNet layers' mixers: the
scopes ``gdn_proj`` (input norm, projections, the short convolution, L2 norms,
decay and write strength), ``gdn_scan`` (the gated delta rule) and ``gdn_out``
(the gated norm and ``W_o``), forward, backward and the blocks' rematerialised
forward; the routed part of those layers lies outside."""

from chipbench import qwen3_next_scopes

NAME = "gdn_device_ms"
UNIT = "ms"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    return qwen3_next_scopes.ms_per_step(evidence, ("gdn_proj", "gdn_scan", "gdn_out"))
