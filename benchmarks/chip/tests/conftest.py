"""Fixtures of the benchmark's own tests (run with
`JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests`)."""
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a configuration small enough for the CPU: test-2bit (n=64, N=512) with
# 8-bit integers of 1-bit digits, two requests in flight
TINY = {
    "name": "tiny", "family": "radix", "source": "test-2bit",
    "params_name": "test-2bit", "n": 64, "N": 512, "k": 1, "width": 2,
    "padding_bits": 1, "pbs_base_log": 12, "pbs_level": 2,
    "ks_base_log": 4, "ks_level": 5, "lwe_std": 2.0 ** -45,
    "glwe_std": 2.0 ** -45, "bits": 8, "msg_bits": 1,
    "server": {"shards": 1, "max_inflight": 2},
    "check": {"noise_share": 0.5}, "reduced": [],
}


def tiny_bench(root: str, traffic: dict) -> str:
    """A copy of the benchmark under `root` with the tiny configuration,
    one traffic mix and one cell per mix; returns its BENCHMARK.json."""
    shutil.copytree(CHIP, root, ignore=shutil.ignore_patterns(
        ".out", "tests", "__pycache__"))
    with open(os.path.join(root, "configs", "tiny.json"), "w") as f:
        json.dump(TINY, f)
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["workloads"] = []
    for name, mix in traffic.items():
        with open(os.path.join(root, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
        bench["workloads"].append({"name": f"tiny.{name}", "config": "tiny",
                                   "traffic": name, "chips": 1,
                                   "why": "CPU rehearsal"})
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = cells
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


OPEN = {"why": "t", "arrivals": {"kind": "stratified_poisson", "rate": 2.0},
        "clients": 4, "ops": {"add": 1, "mul": 1, "relu": 1},
        "operands": "uniform"}
CLOSED = {"why": "t", "arrivals": {"kind": "closed", "think_s": 0.0},
          "clients": 3, "pool_per_client": 40,
          "ops": {"add": 1, "mul": 1, "relu": 1}, "operands": "uniform"}


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench") / "chip")
    return root, tiny_bench(root, {"open": OPEN, "closed": CLOSED})
