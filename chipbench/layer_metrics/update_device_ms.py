"""Device time of the jitted update program per executed step (trace:
``XLA Modules`` events whose name matches the traffic mix's
``programs.update``, mean over the devices)."""

from chipbench.trace_reduce import program_matching

NAME = "update_device_ms"
UNIT = "ms"
LAYER = "L5 update"
SOURCE = "device_trace"
MOVES = "train_frames_per_s"


def update_program(evidence):
    trace, pattern = evidence.get("trace"), evidence.get("programs", {}).get("update")
    if trace is None or not pattern:
        return None
    prog = program_matching(trace, pattern)
    return prog if prog and prog["count"] > 0 else None


def read(evidence):
    prog = update_program(evidence)
    return None if prog is None else 1e3 * prog["seconds"] / prog["count"]
