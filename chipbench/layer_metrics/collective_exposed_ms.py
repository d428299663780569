"""The part of ``collective_ms`` during which no other op runs on that
device: what the collectives add to the step."""

from chipbench.layer_metrics import collective_ms

NAME = "collective_exposed_ms"
UNIT = "ms"
LAYER = "L1 runtime"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    return collective_ms.read(evidence, key="collective_exposed_s")
