"""The gated delta rule's share of its roofline: the least time the chip could
take for the rule's work in a minibatch step, the larger of its needed FLOPs
over the bf16 peak and its needed bytes over the memory bandwidth of
``peaks.json`` (``flops_qwen3_next.gdn_step``: the chunked form at 64 in the
three linear layers, forward, the blocks' rematerialised forward and the
backward at twice a forward; q, k, v, g, beta and o once each and the state at
every chunk boundary, in float32), over the device time under the scope
``gdn_scan``.  The counts read the algorithm's shapes, whatever implements it;
at an episode of 8,192 positions the bytes bound it (about 50 FLOP a byte
against a ridge of 240)."""

from chipbench import qwen3_next_scopes
from chipbench.flops_qwen3_next import roofline_seconds
from chipbench.peaks import peaks_for

NAME = "gdn_scan_roofline_pct"
UNIT = "%"
LAYER = "L6 kernels"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    need = evidence.get("gdn_step")
    seconds = qwen3_next_scopes.seconds_per_step(evidence, ("gdn_scan",))
    if need is None or not seconds:
        return None
    peaks = peaks_for(evidence["device_kind"])
    return 100.0 * roofline_seconds(need["flops"], need["bytes"], peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]) / seconds
