"""One cell end to end at a tiny window on the CPU, with the harness's look
for a chip steered inside the test."""
import functools
import json

import jax
import pytest

import cells
import run

PEAK = {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_no_tpu_exits_non_zero_and_prints_nothing(capsys):
    assert run.main(["--workload", "radix16-msg2carry2.solo", "--seed", "1",
                     "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


@pytest.fixture
def steered(tiny, monkeypatch):
    root, bp = tiny
    monkeypatch.setattr(run, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(run, "device_peak", lambda d: PEAK)
    monkeypatch.setattr(run.cells, "load_cell", functools.partial(
        cells.load_cell, bench_path=bp, root=root))
    return root, bp


@pytest.mark.parametrize("cell,trace", [("tiny.open", 0), ("tiny.closed", 1)])
def test_cell_prints_a_well_formed_last_line(steered, capsys, cell, trace):
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 3),
                   "--seconds", "3", "--trace", str(trace)])
    assert rc == 0
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["checks"]) == {"missing", "wrong", "noise_share"}
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    loaded = cells.load_cell(cell)
    want = loaded.per_layer if trace else loaded.end_to_end
    for m in want:
        if m.entry["source"] == "device_trace":
            continue            # no device in a CPU trace
        assert res["metrics"][m.name]["unit"] == m.unit
        assert res["metrics"][m.name]["value"] >= 0
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert res["metrics"]["setup_s"]["value"] > 0
    err = out.err.strip().splitlines()
    assert err[-3:] == [f"[bench] check {k} {res['checks'][k]['value']} "
                        f"limit {res['checks'][k]['limit']}"
                        for k in ("missing", "wrong", "noise_share")]
