"""The readers of the engine room, the host path and the compile count, on
a synthetic window laid out as the program records it: `engine_room`
spans on one lane per device, other spans beside them, and the `jit.*`
counters.  Every expected number is worked out by hand."""
import pytest

import cells
import run
from conftest import CHIP
from repro.obs import SpanEvent

MS = 1e-3


def _reader(name):
    return cells.load_module(f"{CHIP}/metrics/{name}.py", "t_")


def _room(program, ts_ms, dur_ms, padded, lane="engine-room tpu:0"):
    return SpanEvent("engine_room", "engine", ts_ms * MS, dur_ms * MS, 3,
                     lane, {"program": program, "rows": padded - 1,
                            "padded": padded, "queued_ms": 0.0,
                            "round": None})


def _window(spans, counters):
    return run.Window(False, 51.0, 0.0, 51.0, [], counters, spans, None,
                      None, {}, 1.0, 0.0, (0, 0.0, 0, 0))


# one device: a keyswitch, its 16-row pbs_batch_small 0 ms later, then
# 5 ms of host work before a 32-row pbs_batch, with a host span and a
# compile beside them; a second device runs one 16-row round after a
# 2 ms gap
SPANS = [
    _room("keyswitch_batch_jit", 0.0, 10.0, 16),
    _room("pbs_batch_small", 10.0, 800.0, 16),
    SpanEvent("row_keys", "sched", 811 * MS, 3 * MS, 1, "w", {}),
    _room("pbs_batch", 815.0, 400.0, 32),
    _room("keyswitch_batch_jit", 0.0, 10.0, 16, lane="engine-room tpu:1"),
    _room("pbs_batch_small", 12.0, 800.0, 16, lane="engine-room tpu:1"),
]


def test_engine_room_ms_per_row_is_busy_time_over_padded_pbs_rows():
    # (10 + 800 + 400 + 10 + 800) ms over 16 + 32 + 16 padded PBS rows
    got = _reader("engine_room_ms_per_row.latency").read(
        _window(SPANS, {}))
    assert got == pytest.approx(2020.0 / 64)


def test_host_ms_per_round_is_idle_between_spans_per_pbs_round():
    # device 0 idles 0 + 5 ms, device 1 idles 2 ms; three PBS rounds
    got = _reader("host_ms_per_round.latency").read(_window(SPANS, {}))
    assert got == pytest.approx(7.0 / 3)
    # the order in which the spans were read does not matter
    assert _reader("host_ms_per_round.latency").read(
        _window(SPANS[::-1], {})) == pytest.approx(7.0 / 3)


def test_serving_compiles_reads_the_window_counter():
    read = _reader("serving_compiles.latency").read
    assert read(_window([], {"jit.compiles": 0})) == 0
    assert read(_window([], {"jit.compiles": 3, "sched.fused_rounds": 9})) \
        == 3


@pytest.mark.parametrize("name", ["engine_room_ms_per_row.latency",
                                  "host_ms_per_round.latency",
                                  "serving_compiles.latency"])
def test_a_program_without_the_spans_and_counter_reads_none(name):
    other = [SpanEvent("request", "serve", 0.0, 1.0, 1, "w", {})]
    assert _reader(name).read(
        _window(other, {"sched.fused_rounds": 4})) is None
