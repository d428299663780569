"""``replay_feed_device_ms`` in a cell whose headline is ``env_frames_per_s``."""

from chipbench.layer_metrics.replay_feed_device_ms import read  # noqa: F401

NAME = "loop_feed_device_ms"
UNIT = "ms"
LAYER = "L4 replay"
SOURCE = "device_trace"
MOVES = "env_frames_per_s"
