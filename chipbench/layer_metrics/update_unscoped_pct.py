"""Share of the update program's device time in ops that none of the nine
scopes owns (``scope_reduce.UNSCOPED``: loss metrics, the sentinel's checks,
parameter relayouts, ops XLA made itself).  The milliseconds per scope, the
``while`` time per scope and each scope's largest ops go out on an earlier
line."""

from chipbench import harness, scope_reduce

NAME = "update_unscoped_pct"
UNIT = "%"
LAYER = "L5 update"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def read(evidence):
    got = scope_reduce.update_split(evidence)
    if got is None or got["seconds"] <= 0:
        return None
    per_step = 1e3 / got["count"]
    harness.note(update_scopes={
        "steps": got["count"],
        "ms_per_step": {k: v * per_step for k, v in got["self_s"].items()},
        "while_ms_per_step": {k: v * per_step for k, v in got["while_s"].items()},
        "top_ops_ms_per_step": {k: [[name, s * per_step] for name, s in ops]
                                for k, ops in scope_reduce.top_ops(got).items()},
    })
    return 100.0 * got["self_s"].get(scope_reduce.UNSCOPED, 0.0) / got["seconds"]
