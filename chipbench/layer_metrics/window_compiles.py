"""Compiles inside the measured window (``RecompileMonitor.snapshot()``
delta).  Must be 0: anything else also makes the run incorrect."""

NAME = "window_compiles"
UNIT = "count"
LAYER = "L0 entry"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(evidence):
    value = evidence.get("window_compiles")
    return None if value is None else float(value)
