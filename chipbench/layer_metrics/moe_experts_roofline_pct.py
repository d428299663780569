"""The grouped products' share of their roofline: the FLOPs the held experts'
three products need for the assignments the update's counter counted, forward
and backward (``flops_sdar``: 3 x 2 x 3 x hidden x width an assignment, the
same work whatever implements them), over the device time of ``moe_experts``
(the ops under that scope, and the compiler's ``ragged-dot-*`` ops, which carry
no scope: ``sdar_scopes``), against the bf16 peak of ``peaks.json``.  Compute-bound at
even load (about 10 GFLOP over about 23 MB an expert forward: over 400 FLOP a
byte against a ridge of 240); an expert that few assignments reach is
bandwidth-bound, and the share shows it."""

from chipbench import sdar_scopes

NAME = "moe_experts_roofline_pct"
UNIT = "%"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    return sdar_scopes.roofline_pct(evidence, evidence.get("moe", {}).get("expert_flops_per_step"), ("moe_experts",))
