"""Find a cell's parts by name.

`BENCHMARK.json` at the root of the repository names every cell, metric and
configuration; the files behind the names sit under this directory:

  configs/<config>.json   the configuration as it is run
  traffic/<mix>.json      the traffic mix, read by `loadgen.make_plan`
  programs/<family>.py    the served programs and their plain reference
  metrics/<metric>.py     one reader per metric: `read(run)` returns the
                          number, or None where the run has nothing to read

Adding a configuration, a mix or a metric is adding its file and its entry
in `BENCHMARK.json`; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, prefix: str):
    """Import a file by path under a private module name."""
    name = prefix + "".join(c if c.isalnum() else "_"
                            for c in os.path.relpath(path, HERE))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    entry: dict                 # the BENCHMARK.json entry
    reader: object              # the module of metrics/<name>.py

    @property
    def name(self) -> str:
        return self.entry["name"]

    @property
    def unit(self) -> str:
        return self.entry["unit"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    family: object              # the module of programs/<family>.py
    end_to_end: list            # Metric, the cell's end-to-end metrics
    per_layer: list             # Metric, the cell's per-layer metrics


def applies(entry: dict, cell: str, e2e_names=None) -> bool:
    """A metric with a `workloads` key applies to the cells it lists; a
    per-layer metric without one to every cell that reports the end-to-end
    metric it moves; an end-to-end metric without one to every cell."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    if e2e_names is not None:
        return entry["moves"] in e2e_names
    return True


def load_cell(name: str, bench_path: str | None = None,
              root: str = HERE) -> Cell:
    bench = load_json(bench_path or os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    config = load_json(os.path.join(root, "configs", w["config"] + ".json"))
    traffic = load_json(os.path.join(root, "traffic", w["traffic"] + ".json"))
    family = load_module(os.path.join(root, "programs",
                                      config["family"] + ".py"), "bench_")
    reader = lambda m: Metric(m, load_module(
        os.path.join(root, "metrics", m["name"] + ".py"), "bench_"))
    e2e = [reader(m) for m in bench["end_to_end"] if applies(m, name)]
    e2e_names = {m.name for m in e2e}
    per_layer = [reader(m) for m in bench["per_layer"]
                 if applies(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic, family, e2e,
                per_layer)
