"""``collect_read_roofline_pct`` with the held experts a pass reaches COUNTED
by the program over the window's own rollouts (``reach_collect.py``: the
``experts_reached`` counter), not estimated from the update's routing of the
first rollout: the bytes a rollout's cached passes need from HBM
(``bytes_collect``: every block's parameters once a pass at the compute width,
of the held experts the counted mean; the cache at its mean length; the head
once a scored pass) over the chip's HBM bandwidth of ``peaks.json``, against
the device time of the scopes that do that work (``collect_denoise`` +
``collect_commit`` or ``collect_decode``, and ``collect_score``).
Bandwidth-bound: 4 rows a pass are 4 FLOP a byte of weights.  None for a
program without the counter or the scopes."""

from chipbench import collect_scopes, reach_collect
from chipbench.peaks import peaks_for

NAME = "collect_counted_roofline_pct"
UNIT = "%"
LAYER = "L3 collect"
SOURCE = "device_trace"
MOVES = "env_frames_per_s"


def read(evidence):
    seconds = collect_scopes.seconds_per_rollout(evidence, collect_scopes.MODEL)
    needed = reach_collect.rollout_bytes(evidence)
    if not seconds or needed is None:
        return None
    return 100.0 * needed["rollout"] / peaks_for(evidence["device_kind"])["hbm_bytes_per_s"] / seconds
