"""Model-FLOP utilisation of the update: the FLOPs the forward and backward
passes need per step (``chipbench/flops.py``, recompute excluded) x steps per
second of the run's window / (chips x the bf16 peak of ``peaks.json``)."""

from chipbench.flops import mfu_percent
from chipbench.peaks import peaks_for

NAME = "update_mfu_pct"
UNIT = "%"
LAYER = "L5 update"
SOURCE = "host_clock"
MOVES = "train_frames_per_s"


def read(evidence):
    if "flops_per_step" not in evidence:
        return None
    peak = peaks_for(evidence["device_kind"])["bf16_flops_per_s"]
    return mfu_percent(evidence["flops_per_step"], evidence["steps_per_s"], evidence["chips"], peak)
