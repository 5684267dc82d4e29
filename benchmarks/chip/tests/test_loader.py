"""The cell loader, and BENCHMARK.json against the benchmark's contract."""
import hashlib
import json
import os
import re
import shutil

import pytest

import cells
from conftest import CHIP, REPO

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_config_mix_and_metric_are_new_files_only(tmp_path):
    root = str(tmp_path / "chip")
    shutil.copytree(CHIP, root, ignore=shutil.ignore_patterns(
        ".out", "__pycache__"))
    before = _digest(root)
    cfg = json.load(open(os.path.join(root, "configs",
                                      "radix16-msg2carry2.json")))
    cfg["name"] = "throwaway"
    json.dump(cfg, open(os.path.join(root, "configs", "throwaway.json"), "w"))
    json.dump({"why": "t", "arrivals": {"kind": "poisson", "rate": 0.5},
               "clients": 2, "ops": {"add": 1}, "operands": "uniform"},
              open(os.path.join(root, "traffic", "trickle.json"), "w"))
    with open(os.path.join(root, "metrics", "answered.py"), "w") as f:
        f.write('LAYER = "router (serve/runtime.py)"\n'
                'UNIT, SOURCE, BETTER, MOVES = "req", "host_clock", '
                '"higher", "latency_p50_s"\n'
                "def read(run):\n    return 42\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="throwaway"))
    for name in ("throwaway.trickle", "throwaway.quiet"):
        bench["workloads"].append({"name": name, "config": "throwaway",
                                   "traffic": "trickle", "chips": 1,
                                   "why": "t"})
    bench["end_to_end"][0]["workloads"].append("throwaway.trickle")
    bench["per_layer"].append({"name": "answered", "unit": "req",
                               "better": "higher", "source": "host_clock",
                               "layer": "router (serve/runtime.py)",
                               "moves": "latency_p50_s"})
    bp = str(tmp_path / "BENCHMARK.json")
    json.dump(bench, open(bp, "w"))
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    cell = cells.load_cell("throwaway.trickle", bp, root)
    assert cell.config["name"] == "throwaway"
    assert cell.traffic["arrivals"]["rate"] == 0.5
    assert [m.name for m in cell.end_to_end] == ["latency_p50_s", "setup_s"]
    assert [m.name for m in cell.per_layer] == ["answered"]
    assert cell.per_layer[0].reader.read(None) == 42
    # the metric without a `workloads` key reaches every cell reporting
    # the end-to-end metric it moves, and no other
    assert "answered" in [m.name for m in cells.load_cell(
        "radix16-msg2carry2.solo", bp, root).per_layer]
    quiet = cells.load_cell("throwaway.quiet", bp, root)
    assert [m.name for m in quiet.end_to_end] == ["setup_s"]
    assert "answered" not in [m.name for m in quiet.per_layer]


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith("benchmarks/chip/")
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        for k in c["reduced"]:
            assert NAME.match(k) and not k.endswith(("_dim", "_rank"))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_loads_and_reports_enough():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = cells.load_cell(w["name"])
        names = [m.name for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m.entry["moves"] in names
            assert e2e[m.entry["moves"]]
        cfg = cell.config
        changed = {k for k, v in cfg["source_values"].items()
                   if cfg[k] != v}
        assert changed == set(cfg["reduced"])
        by_name = {c["name"]: c for c in BENCH["configs"]}
        assert by_name[w["config"]]["reduced"] == cfg["reduced"]


@pytest.mark.parametrize("entry", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_reader_declares_what_benchmark_json_says(entry):
    mod = cells.load_module(
        os.path.join(CHIP, "metrics", entry["name"] + ".py"), "t_")
    assert mod.UNIT == entry["unit"]
    assert mod.SOURCE == entry["source"]
    assert mod.BETTER == entry["better"]
    if "layer" in entry:
        assert mod.LAYER == entry["layer"]
        assert mod.MOVES == entry["moves"]
