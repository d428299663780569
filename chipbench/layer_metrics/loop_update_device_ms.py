"""``update_device_ms`` in a cell whose headline is ``env_frames_per_s``: it
moves that headline by at most the update's share of the wall."""

from chipbench.layer_metrics.update_device_ms import read  # noqa: F401

NAME = "loop_update_device_ms"
UNIT = "ms"
LAYER = "L5 update"
SOURCE = "device_trace"
MOVES = "env_frames_per_s"
