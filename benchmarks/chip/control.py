#!/usr/bin/env python3
"""The control and the planted faults: runs of a cell in which the check has
to come out not correct.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <a> <b> <c> [--plant control]

runs the cell once per seed in one process, with the plant switched on, and
prints one JSON line per run with its checks.  The benchmark's own runs
never plant anything.

  control    the program with its transform-domain bootstrapping key held at
             float32 precision (rounded from float64, the type the
             configuration states): the step that would tempt a change
             that halves the key bytes.  Same shapes, so the same compiled
             programs run.
  unchanged  every CMux step returns the accumulator unchanged (the
             external product adds nothing).
  half       each engine-room call computes the first half of its rows and
             hands the first half's answers back for the rest.
  altered    one answer altered where it is produced: the first row of
             every engine-room call gets delta added to its body.

A cell on one chip has no exchange between chips to leave out.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import cells  # noqa: E402
import client  # noqa: E402
import run  # noqa: E402


def _clear():
    import jax
    jax.clear_caches()


@contextlib.contextmanager
def control():
    """The Fourier bootstrapping key rounded to float32 precision."""
    import jax.numpy as jnp
    make = client.make_context

    def rounded(seed, p):
        ctx = make(seed, p)
        ctx.bsk_f = ctx.bsk_f.astype(jnp.float32).astype(jnp.float64)
        return ctx

    client.make_context = rounded
    try:
        yield
    finally:
        client.make_context = make


@contextlib.contextmanager
def unchanged():
    """Every CMux step leaves the accumulator as it was."""
    import jax.numpy as jnp
    from repro.core import ggsw
    ext = ggsw.external_product_fourier
    ggsw.external_product_fourier = lambda g, ct, *a: jnp.zeros_like(ct)
    _clear()
    try:
        yield
    finally:
        ggsw.external_product_fourier = ext
        _clear()


@contextlib.contextmanager
def half():
    """Each engine-room call computes only the first half of its rows."""
    import jax.numpy as jnp
    from repro.core import batch
    orig = {n: getattr(batch, n) for n in ("pbs_batch", "pbs_batch_small")}

    def halved(fn):
        def call(x, polys, *rest, **kw):
            h = max(1, x.shape[0] // 2)
            out = fn(x[:h], polys[:h], *rest, **kw)
            reps = -(-x.shape[0] // h)
            return jnp.concatenate([out] * reps)[:x.shape[0]]
        call.lower = fn.lower
        return call

    for n, fn in orig.items():
        setattr(batch, n, halved(fn))
    try:
        yield
    finally:
        for n, fn in orig.items():
            setattr(batch, n, fn)


@contextlib.contextmanager
def altered():
    """The first row of every engine-room call is off by one digit step."""
    import jax.numpy as jnp
    from repro.core.engine import TaurusEngine
    orig = {n: getattr(TaurusEngine, n) for n in ("lut_batch",
                                                  "lut_batch_small")}

    def shifted(fn):
        def call(self, *a):
            out = fn(self, *a)
            d = jnp.uint64(client.delta(self.params))
            return out.at[0, -1].add(d)
        return call

    for n, fn in orig.items():
        setattr(TaurusEngine, n, shifted(fn))
    try:
        yield
    finally:
        for n, fn in orig.items():
            setattr(TaurusEngine, n, fn)


PLANTS = {"control": control, "unchanged": unchanged, "half": half,
          "altered": altered}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--plant", choices=sorted(PLANTS), default="control")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    try:
        devices = run.require_chips(cell.chips)
    except run.NoChip as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(run.REPO, "src"))
    from repro.runtime import compile_cache
    with PLANTS[args.plant]():
        for seed in args.seeds:
            res = run.run_cell(cell, devices, seed, args.seconds, False,
                               compile_cache)
            print(json.dumps({"plant": args.plant, "seed": seed,
                              "correct": res["correct"],
                              "attempted": res["attempted"],
                              "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
