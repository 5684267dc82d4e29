"""How long the engine room waited on the host, per PBS round, in the
cells that report a latency: over the program's `engine_room` spans that
start in the window, taken in order on each device, the idle time
between consecutive spans (start_k - end_{k-1}: the host had not yet
enqueued execution k when k-1 was done), summed, over the number of PBS
executions among them.  Request boundaries are included; the window's
length is not used.  None where the program records no such span."""
LAYER = "host path (api/session.py to serve/scheduler.py)"
UNIT, SOURCE, BETTER, MOVES = "ms", "program_span", "lower", \
    "latency_p50_s"


def read(run):
    lanes = {}
    for s in run.spans:
        if s.name == "engine_room":
            lanes.setdefault(s.thread, []).append(s)
    rounds = sum(s.args["program"].startswith("pbs_")
                 for lane in lanes.values() for s in lane)
    if not rounds:
        return None
    idle = 0.0
    for lane in lanes.values():
        lane.sort(key=lambda s: s.ts)
        idle += sum(max(0.0, b.ts - (a.ts + a.dur))
                    for a, b in zip(lane, lane[1:]))
    return 1e3 * idle / rounds
