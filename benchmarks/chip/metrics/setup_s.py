"""Seconds from process start to the window's start: keys, tracing, the
compile or cache load of every program shape, warm-up and encryption of
the requests' inputs.  Host clock."""
UNIT, SOURCE, BETTER = "s", "host_clock", "lower"


def read(run):
    return run.setup_s
