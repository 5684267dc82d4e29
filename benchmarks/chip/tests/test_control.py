"""The control and each planted fault turn `correct` false; the same run
without a plant is correct."""
import jax
import pytest

import cells
import control
import run
from repro.runtime import compile_cache

PEAK = {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def open_cell(tiny, monkeypatch):
    root, bp = tiny
    monkeypatch.setattr(run, "device_peak", lambda d: PEAK)
    return cells.load_cell("tiny.open", bp, root)


def _run(cell, seed):
    return run.run_cell(cell, jax.devices()[:1], seed, 3.0, False,
                        compile_cache)


@pytest.mark.parametrize("plant", ["control", "unchanged", "half",
                                   "altered"])
def test_plant_is_not_correct(open_cell, plant):
    with control.PLANTS[plant]():
        res = _run(open_cell, 21)
    assert res["attempted"] > 0
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["wrong"]["value"] > 0 or \
        res["checks"]["noise_share"]["value"] > \
        res["checks"]["noise_share"]["limit"]


def test_without_plant_is_correct(open_cell):
    res = _run(open_cell, 21)
    assert res["correct"] is True, res["checks"]
