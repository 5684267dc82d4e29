"""Share of the traced window in which no program ran on the chip
(1 - busy / window), in the cells that report a latency."""
import measures

LAYER = "device (XLA:TPU)"
UNIT, SOURCE, BETTER, MOVES = "%", "device_trace", "lower", "latency_p50_s"


def read(run):
    return measures.idle_share(run.trace)
