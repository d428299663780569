"""Share of the loop's wall inside the program's ``Time/env_interaction_time``
timer (env step, player step, replay add), over the telemetry records of the
steady part of the run."""

NAME = "loop_env_pct"
UNIT = "%"
LAYER = "L3 collect"
SOURCE = "program_span"
MOVES = "env_frames_per_s"
TIMER = "Time/env_interaction_time"


def shares(evidence):
    """{timer: seconds} and the wall they were taken over, from consecutive
    telemetry records that both lie after the first gradient step."""
    records = [r for r in evidence.get("telemetry", []) if r.get("train_step", 0) > 0 and "ts" in r]
    if len(records) < 2:
        return None
    wall = records[-1]["ts"] - records[0]["ts"]
    if wall <= 0:
        return None
    sums = {}
    for r in records[1:]:
        for k, v in (r.get("timers_s") or {}).items():
            sums[k] = sums.get(k, 0.0) + float(v)
    return sums, wall


def read(evidence, timer=TIMER):
    got = shares(evidence)
    if got is None:
        return None
    sums, wall = got
    return 100.0 * sums.get(timer, 0.0) / wall
