"""The whole round's share of the chip's peak, in the cells that report a
latency: the operations the algorithm needs for every PBS row dispatched
in the window (`ops.py`; rows sharing a ciphertext share its keyswitch),
over the window's length times the compute peak of `peaks.json`."""
import ops

LAYER = "whole round (serve/scheduler.py to core/batch.py)"
UNIT, SOURCE, BETTER, MOVES = "%", "program_counter", "higher", \
    "latency_p50_s"


def read(run):
    c = run.counters
    rows = c.get("sched.dispatched_luts", 0)
    if not rows:
        return None
    work = ops.RoundWork(rows, rows - c.get("sched.ks_dedup_hits", 0))
    return 100.0 * work.ops(run.params) / (run.seconds
                                           * run.peak["ops_per_s"])
