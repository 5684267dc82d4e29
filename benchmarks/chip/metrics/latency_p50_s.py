"""Median time from each request's due time (open loop) or send time
(closed loop) to its outputs being ready on the device, over every request
due in the window.  Host clock."""
import measures

UNIT, SOURCE, BETTER = "s", "host_clock", "lower"


def read(run):
    return measures.quantile([r.latency for r in run.in_window()], 0.5)
