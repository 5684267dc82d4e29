"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,table2,...]

Writes benchmarks/results.json and prints each table with paper
comparisons inline.  Serving rows additionally land in
benchmarks/BENCH_serve.json (requests/sec, fused-batch occupancy, dedup
hit-rate, p50/p99 latency, BSK bytes saved) so the serving perf
trajectory is tracked machine-readably.

Exit code: non-zero when ANY selected benchmark module fails (partial
results are still written so the surviving rows aren't lost, but a
partial run must never look green to CI) — `tests/test_obs.py` pins
this contract.  `--dry-run` additionally checks that both serve
benchmarks declare the observability columns and that the Chrome-trace
exporter round-trips.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

ALL = ["fig5", "table2", "table4", "fig13", "fig15", "dedup", "engine",
       "kernels", "radix", "serve", "fhe_ml", "sim"]

# the observability columns every serve-bench row gained in the
# repro.obs PR; the dry run fails if a serve benchmark stops declaring
# them (BENCH_serve.json consumers key on these)
SERVE_OBS_COLUMNS = ("p50_s", "p99_s", "bsk_bytes_saved")
SERVE_BENCH_NAMES = ("serve", "fhe_ml")

# the columns every point of the serve benchmark's shard_scaling row
# must carry (the sharded-serving PR's dry-run contract; the nightly
# shard sweep's BENCH_serve.json consumers key on these)
SERVE_SCALING_COLUMNS = ("shards", "requests_per_s", "per_shard_occupancy",
                         "occupancy_ratio")

# the SLO columns every sim row must carry (BENCH_sim.json consumers
# key on these; the repro.sim PR's dry-run contract)
SIM_SLO_COLUMNS = ("p50_s", "p99_s", "queue_wait_p99_s", "abandon_rate",
                   "goodput_rps", "slo_ok", "virtual_deterministic")

# the columns every kernel row must carry (BENCH_kernels.json consumers
# key on these; the Pallas engine-room PR's dry-run contract)
KERNEL_COLUMNS = ("ref_ms", "pallas_ms", "speedup", "bytes_streamed",
                  "bytes_bound", "bytes_ok", "reuse_factor")


def _default_mods() -> dict:
    from benchmarks import (fig5_addition, table2_workloads, table4_xpu,
                            fig13_bandwidth, fig15_utilization, dedup_stats,
                            engine_wallclock, fhe_ml_serve, kernels_bench,
                            radix_throughput, serve_throughput, sim_slo)
    return {"fig5": fig5_addition, "table2": table2_workloads,
            "table4": table4_xpu, "fig13": fig13_bandwidth,
            "fig15": fig15_utilization, "dedup": dedup_stats,
            "engine": engine_wallclock, "kernels": kernels_bench,
            "radix": radix_throughput,
            "serve": serve_throughput, "fhe_ml": fhe_ml_serve,
            "sim": sim_slo}


def _dry_run_checks(mods: dict, which: list) -> list:
    """Entry-point + observability checks, no benchmark execution.
    Returns a list of problems (empty == pass)."""
    bad = [f"{n}: missing run()" for n in which
           if not callable(getattr(mods[n], "run", None))]
    for n in SERVE_BENCH_NAMES:
        if n not in which:
            continue
        cols = tuple(getattr(mods[n], "BENCH_COLUMNS", ()))
        missing = [c for c in SERVE_OBS_COLUMNS if c not in cols]
        if missing:
            bad.append(f"{n}: BENCH_COLUMNS missing {missing}")
    if "serve" in which:
        cols = tuple(getattr(mods["serve"], "SCALING_COLUMNS", ()))
        missing = [c for c in SERVE_SCALING_COLUMNS if c not in cols]
        if missing:
            bad.append(f"serve: SCALING_COLUMNS missing {missing}")
    if "sim" in which:
        cols = tuple(getattr(mods["sim"], "BENCH_COLUMNS", ()))
        missing = [c for c in SIM_SLO_COLUMNS if c not in cols]
        if missing:
            bad.append(f"sim: BENCH_COLUMNS missing {missing}")
    if "kernels" in which:
        cols = tuple(getattr(mods["kernels"], "BENCH_COLUMNS", ()))
        missing = [c for c in KERNEL_COLUMNS if c not in cols]
        if missing:
            bad.append(f"kernels: BENCH_COLUMNS missing {missing}")
        # the roofline model the kernel rows are gated by must build
        try:
            from repro.core.params import TEST_PARAMS
            from repro.launch.roofline import pbs_round_model
            model = pbs_round_model(TEST_PARAMS, 12)
            assert model.fused_bytes < model.unfused_bytes
        except Exception as err:  # noqa: BLE001 — any breakage fails the check
            bad.append(f"kernels roofline model: {err!r}")
    # the trace exporter the CI smoke lane relies on must round-trip
    try:
        from repro.obs import Telemetry, validate_chrome_trace
        tel = Telemetry(trace=True)
        with tel.span("dry_run_check", cat="bench"):
            pass
        validate_chrome_trace(json.dumps(tel.chrome_trace()))
    except Exception as err:  # noqa: BLE001 — any breakage fails the check
        bad.append(f"chrome-trace exporter: {err!r}")
    return bad


def main(argv=None, mods: dict | None = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--dry-run", action="store_true",
                    help="import every benchmark module, resolve its run() "
                         "entry point, and check the serve benchmarks' "
                         "observability columns + trace exporter without "
                         "executing (CI: keeps the entry points from "
                         "bit-rotting)")
    ap.add_argument("--out-dir", default=None,
                    help="directory for results.json / BENCH_serve.json "
                         "(default: the benchmarks package directory)")
    args = ap.parse_args(argv)
    which = args.only.split(",") if args.only else ALL

    if mods is None:
        mods = _default_mods()
    unknown = [n for n in which if n not in mods]
    if unknown:
        print(f"[benchmarks] unknown benchmark(s) {unknown} "
              f"(have {sorted(mods)})")
        return 2

    if args.dry_run:
        bad = _dry_run_checks(mods, which)
        print(f"[benchmarks] dry-run: {len(which)} modules checked, "
              f"{len(bad)} problems {bad}")
        return 1 if bad else 0

    out_dir = args.out_dir or os.path.dirname(__file__)
    results, failed = [], []
    for name in which:
        try:
            results.extend(mods[name].run())
        except Exception:
            traceback.print_exc()
            failed.append(name)
    path = os.path.join(out_dir, "results.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=1, default=float)
    if any(r.get("bench") == "serve" for r in results):
        from benchmarks.serve_throughput import write_bench_json
        spath = write_bench_json(
            results, path=os.path.join(out_dir, "BENCH_serve.json"))
        print(f"[benchmarks] serving rows -> {spath}")
    if any(r.get("bench") == "sim" for r in results):
        from benchmarks.sim_slo import write_bench_json as write_sim_json
        spath = write_sim_json(
            results, path=os.path.join(out_dir, "BENCH_sim.json"))
        print(f"[benchmarks] sim SLO rows -> {spath}")
    if any(r.get("bench") == "kernels" for r in results):
        from benchmarks.kernels_bench import write_bench_json as write_k_json
        spath = write_k_json(
            results, path=os.path.join(out_dir, "BENCH_kernels.json"))
        print(f"[benchmarks] kernel rows -> {spath}")
    print(f"\n[benchmarks] {len(results)} rows -> {path}; "
          f"{len(failed)} failed {failed}")
    # a partial run keeps its rows but must exit non-zero: CI treats any
    # failed module as a red run, not a quieter green one
    return 1 if failed else 0


if __name__ == "__main__":
    from repro.runtime import compile_cache
    compile_cache.enable()
    sys.exit(main())
