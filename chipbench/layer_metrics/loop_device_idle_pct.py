"""``device_idle_pct`` in a cell whose headline is ``env_frames_per_s``."""

from chipbench.layer_metrics.device_idle_pct import read  # noqa: F401

NAME = "loop_device_idle_pct"
UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "env_frames_per_s"
