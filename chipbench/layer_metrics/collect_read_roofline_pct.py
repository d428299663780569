"""The model's share of collection against what it has to read: the bytes a
rollout's cached passes need from HBM whatever implements them
(``bytes_collect``: every block's parameters once a pass at the configuration's
compute width, of the held experts those a pass's rows reach, counted from the
first rollout's routing; the cache at its mean length, the head once a scored
pass; the few positions' activations are noise), over the chip's HBM bandwidth of
``peaks.json``, against the device time of the scopes that do that work
(``collect_denoise`` + ``collect_commit`` or ``collect_decode``, and
``collect_score``).  Bandwidth-bound: 48 positions a pass are 48 FLOP a byte
of weights against a ridge of 240."""

from chipbench import collect_scopes
from chipbench.peaks import peaks_for

NAME = "collect_read_roofline_pct"
UNIT = "%"
LAYER = "L3 collect"
SOURCE = "device_trace"
MOVES = "env_frames_per_s"


def read(evidence):
    seconds = collect_scopes.seconds_per_rollout(evidence, collect_scopes.MODEL)
    needed = evidence.get("collect", {}).get("rollout_bytes", {}).get("rollout")
    if not seconds or not needed:
        return None
    return 100.0 * needed / peaks_for(evidence["device_kind"])["hbm_bytes_per_s"] / seconds
