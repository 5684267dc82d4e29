"""Device time of the engine room per padded PBS row, in the cells that
report a latency: over the program's `engine_room` spans that start in
the window (one per engine-room execution, its busy interval on the
device, keyswitches included), their summed length over the padded rows
of the PBS executions among them.  Whole spans only, never the window's
length: a round lasts 0.8-5 s of the window, and a ratio over whole
spans is not biased by where the window cuts.  None where the program
records no such span."""
LAYER = "engine room (core/batch.py)"
UNIT, SOURCE, BETTER, MOVES = "ms", "program_span", "lower", \
    "latency_p50_s"


def read(run):
    rooms = [s for s in run.spans if s.name == "engine_room"]
    rows = sum(s.args["padded"] for s in rooms
               if s.args["program"].startswith("pbs_"))
    if not rows:
        return None
    return 1e3 * sum(s.dur for s in rooms) / rows
