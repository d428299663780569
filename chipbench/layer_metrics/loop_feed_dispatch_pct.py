"""Share of the loop's wall inside ``Time/feed_dispatch``: what the host does in
``sequence_batches`` before the first batch is handed out (on the ring path
the sampler's dispatch; it does not wait for the device)."""

from chipbench import span_reduce

NAME = "loop_feed_dispatch_pct"
UNIT = "%"
LAYER = "L4 replay"
SOURCE = "program_span"
MOVES = "env_frames_per_s"
TIMER = "Time/feed_dispatch"


def read(evidence):
    return span_reduce.timer_share(evidence, TIMER)
