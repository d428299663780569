"""Device time of the rollout program per iteration (trace: ``XLA Modules``
events whose name matches the traffic mix's ``programs.collect``, mean over the
devices; one rollout an iteration).  None for a program whose rollout has
another name."""

from chipbench.trace_reduce import program_matching

NAME = "collect_device_ms"
UNIT = "ms"
LAYER = "L3 collect"
SOURCE = "device_trace"
MOVES = "env_frames_per_s"


def read(evidence):
    trace, pattern = evidence.get("trace"), evidence.get("programs", {}).get("collect")
    if trace is None or not pattern:
        return None
    prog = program_matching(trace, pattern)
    return 1e3 * prog["seconds"] / prog["count"] if prog and prog["count"] > 0 else None
