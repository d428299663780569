"""Share of the traced steady window in which no op ran on the device: 1 -
(union of device-op intervals) / window, the mean over the devices used (the
worst device goes on an earlier line)."""

NAME = "device_idle_pct"
UNIT = "%"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    trace = evidence.get("trace")
    return None if trace is None else 100.0 * trace["idle_share"]
