"""The engine room's device time, measured off the serving path.

`ExecutionWatcher` takes each engine-room execution's output the moment
it was enqueued and, on a thread of its own per device, waits for the
outputs in enqueue order (`jax.block_until_ready`).  A device runs its
executions in order, so execution k was busy over

    [max(enqueue_k, ready_{k-1}), ready_k]

and that interval is recorded as an `engine_room` span on the watcher's
lane, with `queued_ms` = start - enqueue (how long the execution waited
behind the previous one).  Consecutive spans of one device never
overlap; the time between them is time the device waited on the host.

A recorder makes its watcher on the first `TraceRecorder.watch`, which
only a tracing recorder is asked for: with tracing off no thread starts
and no array is held.  A device's thread exits after `LINGER_S` idle
seconds and starts again with the next execution.
"""
from __future__ import annotations

import queue
import threading
import time

import jax

LINGER_S = 10.0


class _Lane:
    """One device: its queue of executions, its thread, and the ready
    stamp of its last execution."""

    __slots__ = ("q", "thread", "ready", "handed", "done")

    def __init__(self):
        self.q: queue.SimpleQueue = queue.SimpleQueue()
        self.thread = None
        self.ready = float("-inf")
        self.handed = 0
        self.done = 0


def _device_name(out) -> str:
    dev = min(out.sharding.device_set, key=lambda d: d.id)
    return f"{dev.platform}:{dev.id}"


class ExecutionWatcher:
    """Per-device watcher threads recording `engine_room` spans into one
    `TraceRecorder` (see module docstring)."""

    def __init__(self, recorder):
        self._rec = recorder
        self._lanes: dict = {}
        self._cv = threading.Condition()

    def watch(self, out, enqueued: float, args: dict, parent) -> None:
        name = _device_name(out)
        with self._cv:
            lane = self._lanes.get(name)
            if lane is None:
                lane = self._lanes[name] = _Lane()
            lane.handed += 1
            lane.q.put((out, enqueued, args, parent))
            if lane.thread is None:
                lane.thread = threading.Thread(
                    target=self._run, args=(lane,), daemon=True,
                    name=f"engine-room {name}")
                lane.thread.start()

    def _run(self, lane: _Lane) -> None:
        rec = self._rec
        while True:
            try:
                out, enqueued, args, parent = lane.q.get(timeout=LINGER_S)
            except queue.Empty:
                with self._cv:
                    if lane.q.empty():
                        lane.thread = None
                        return
                continue
            try:
                jax.block_until_ready(out)
                ready = time.perf_counter()
                start = max(enqueued, lane.ready)
                lane.ready = ready
                args["queued_ms"] = (start - enqueued) * 1e3
                rec._append("engine_room", "engine", start, ready - start,
                            args, parent)
            except Exception:  # noqa: BLE001 — the caller sees the error
                pass
            finally:
                del out
                with self._cv:
                    lane.done += 1
                    self._cv.notify_all()

    def flush(self) -> None:
        """Wait until every execution handed in so far is recorded."""
        with self._cv:
            want = {lane: lane.handed for lane in self._lanes.values()}
            self._cv.wait_for(lambda: all(lane.done >= n
                                          for lane, n in want.items()))
