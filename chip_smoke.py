#!/usr/bin/env python3
"""Bring-up smoke run of the encrypted serving path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four one-chip shards vs one
                                       # four-device SPMD shard

One process, no CPU fallback: exits non-zero, printing no result, when
JAX finds no TPU or the repository's `src/` is missing.  The one-chip run

  1. prints the JAX / libtpu versions and the device kind;
  2. checks the TPU's engine room on the chip: the int8 external
     product (gadget digits times a GGSW, N = 2048) must equal the exact
     integer product computed on the host bit for bit, at test-4bit's
     and cnn20's decomposition;
  3. generates TEST_PARAMS_4BIT keys on the chip, whose BSK must come
     out in the int8 block layout;
  4. serves two waves of clients through `Session(ctx, backend="serve")`
     — 16-bit radix add, mul and relu, and the radix GPT-2 block — and
     decrypts every output against its plaintext oracle;
  5. prints keygen and first-wave (compile) seconds, the latency of the
     warm wave (a smoke timing, not a benchmark) and the engine's
     `engine.lut_batches*` counters.

`--four-chips` runs only the sharded path: 16 radix clients on
`shards=4` (one engine per chip, keys on its own device) against
`shards=1` with a four-device SPMD mesh engine, and requires identical
decrypts.  Any mismatch, failed request or retried round fails the run.
The last line of standard output is the JSON verdict
`{"ok": true, "device": {"platform", "kind", "count"}}`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
BITS = 16
MOD = 1 << BITS


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def exact_negacyclic(dig, tor):
    """Exact negacyclic product of int digits and uint64 torus values,
    mod 2^64, on the host (numpy uint64 wraps)."""
    import numpy as np
    N = tor.shape[-1]
    idx = np.arange(N)[:, None] - np.arange(N)[None, :]
    sign = np.where(idx < 0, np.uint64(2 ** 64 - 1), np.uint64(1))
    mat = tor[idx % N] * sign                         # +-tor[i - j]
    return (mat * dig.astype(np.int64).astype(np.uint64)[None, :]).sum(
        axis=1, dtype=np.uint64)


def int8_probe(seed: int) -> dict:
    """The int8 external product on the device against the exact
    product on the host, per decomposition; raises unless bit-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import blockmul
    from repro.core.params import PAPER_PARAMS, TEST_PARAMS_4BIT

    rng = np.random.default_rng(seed)
    out = {}
    for p in (TEST_PARAMS_4BIT, PAPER_PARAMS["cnn20"]):
        K, J, half = p.k + 1, (p.k + 1) * p.pbs_level, 1 << (p.pbs_base_log - 1)
        g = rng.integers(0, 2 ** 64, (K, p.pbs_level, K, 2048),
                         dtype=np.uint64)
        dig = rng.integers(-half, half + 1, (2, J, 2048))
        dig[0, :, ::2] = -half
        got = np.asarray(jax.jit(
            lambda g, d: blockmul.external_product(
                blockmul.ggsw_to_blocks(g), d, K, p.pbs_base_log))(
            jnp.asarray(g), jnp.asarray(dig)))
        S = g.reshape(J, K, 2048)
        want = np.zeros_like(got)
        for b in range(2):
            for k in range(K):
                for j in range(J):
                    want[b, k] += exact_negacyclic(dig[b, j], S[j, k])
        out[p.name] = bool(np.array_equal(got, want))
        log(f"int8 probe {p.name}: base_log={p.pbs_base_log} "
            f"level={p.pbs_level} N=2048 exact: {out[p.name]}")
        if not out[p.name]:
            raise AssertionError(f"the int8 external product is not exact "
                                 f"at {p.name}")
    return out


def radix_jobs(sess, rng, n_clients: int, ops=("add", "mul", "relu")):
    """(program, plaintext args, oracle) for n_clients radix clients
    cycling through `ops`."""
    from repro.api import IntSpec
    fns = {"add": (lambda a, b: a + b, 2, lambda a, b: (a + b) % MOD),
           "mul": (lambda a, b: a * b, 2, lambda a, b: (a * b) % MOD),
           "relu": (lambda a: a.relu(), 1,
                    lambda a: a if a < MOD // 2 else 0)}
    progs = {op: sess.trace(fns[op][0], *[IntSpec(BITS)] * fns[op][1])
             for op in ops}
    jobs = []
    for i in range(n_clients):
        op = ops[i % len(ops)]
        args = [int(v) for v in rng.integers(0, MOD, fns[op][1])]
        jobs.append((f"{op}-{i}", progs[op], args, [fns[op][2](*args)]))
    return jobs


def gpt2_job(sess, rng):
    """The radix GPT-2 block client (part 2 of examples/fhe_gpt2.py)."""
    import numpy as np
    from repro.fhe_ml.quantize import calibrate_radix, quantize_to_radix
    from repro.serve.programs import fhe_ml_block_program

    g, meta = fhe_ml_block_program("gpt2", d=2, bits=BITS, msg_bits=2,
                                   seed=1)
    prog = sess.compile(g, meta["in_specs"], meta["out_specs"])
    xf = rng.uniform(-1, 1, size=(2,))
    q = quantize_to_radix(xf, calibrate_radix(xf, BITS, 2,
                                              qmax=meta["input_qmax"]))
    want = np.asarray(meta["int_fn"](q)) % MOD
    return ("gpt2-block", prog, [q], [want])


def serve_wave(sess, jobs, key):
    """Submit every job concurrently, join, decrypt and check each
    output against its oracle and every handle for retries; returns the
    wave's wall time (submit to last decrypt) and the decrypted
    outputs."""
    import jax
    import numpy as np
    t0 = time.perf_counter()
    handles = []
    for i, (name, prog, args, want) in enumerate(jobs):
        enc = sess.encrypt_inputs(jax.random.fold_in(key, i), args, prog)
        handles.append(sess.submit(prog, enc, client_id=name))
    outs = [sess.decrypt_outputs(prog, h.outputs())
            for (_, prog, _, _), h in zip(jobs, handles)]
    wall = time.perf_counter() - t0
    for (name, _, args, want), h, got in zip(jobs, handles, outs):
        if h.retries:
            raise AssertionError(f"{name}: {h.retries} retried rounds")
        for g, w in zip(got, want):
            if not np.array_equal(np.asarray(g) % MOD, np.asarray(w) % MOD):
                raise AssertionError(f"{name}{args}: decrypted {g}, "
                                     f"oracle {w}")
    return wall, outs


def check_runtime(sess) -> dict:
    counters = sess.metrics()["counters"]
    for k in ("serve.failed", "serve.retries"):
        if counters.get(k, 0):
            raise AssertionError(f"{k} = {counters[k]}")
    return counters


def one_chip(ctx_key: int) -> None:
    import jax
    import numpy as np
    from repro.api import Session
    from repro.core import ggsw
    from repro.core.params import TEST_PARAMS_4BIT
    from repro.core.pbs import TFHEContext
    from repro.obs import Telemetry

    int8_probe(ctx_key)
    t0 = time.perf_counter()
    ctx = TFHEContext.create(jax.random.key(ctx_key), TEST_PARAMS_4BIT)
    jax.block_until_ready((ctx.bsk_f, ctx.ksk))
    if ggsw.key_layout(ctx.bsk_f) != "int8_blocks":
        raise AssertionError(f"the chip's BSK is {ctx.bsk_f.dtype}, not "
                             f"the int8 block layout")
    log(f"keygen {TEST_PARAMS_4BIT.name} on {ctx.bsk_f.devices()}: "
        f"{time.perf_counter() - t0:.3f} s (compile included); bsk_f "
        f"{ctx.bsk_f.shape} {ctx.bsk_f.dtype}, ksk {ctx.ksk.shape} "
        f"{ctx.ksk.dtype}")

    tel = Telemetry()
    rng = np.random.default_rng(ctx_key)
    with Session(ctx, backend="serve", telemetry=tel) as sess:
        sess.engine.telemetry = tel
        waves = []
        for w in range(2):
            jobs = radix_jobs(sess, rng, 3) + [gpt2_job(sess, rng)]
            waves.append(serve_wave(sess, jobs,
                                    jax.random.key(1000 + w))[0])
            log(f"wave {w}: {len(jobs)} clients "
                f"({', '.join(j[0] for j in jobs)}) decrypt to their "
                f"oracles in {waves[-1]:.3f} s")
        counters = check_runtime(sess)
    log(f"first wave (compile included): {waves[0]:.3f} s")
    log(f"warm wave latency: {waves[1]:.3f} s — a smoke timing, "
        f"not a benchmark")
    log("engine counters: " + json.dumps(
        {k: v for k, v in counters.items() if k.startswith("engine.")}))
    log("serve counters: " + json.dumps(
        {k: counters[k] for k in ("serve.completed", "serve.failed",
                                  "serve.retries")}))


def four_chips(ctx_key: int) -> None:
    import jax
    import numpy as np
    from repro.api import Session
    from repro.core.engine import TaurusEngine
    from repro.core.params import TEST_PARAMS_4BIT
    from repro.core.pbs import TFHEContext
    from repro.launch.mesh import shard_mesh
    from repro.obs import Telemetry

    devs = jax.devices()
    if len(devs) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, have {devs}")
    ctx = TFHEContext.create(jax.random.key(ctx_key), TEST_PARAMS_4BIT)
    results = {}
    for shards in (4, 1):
        tel = Telemetry()
        engine = (None if shards == 4 else
                  TaurusEngine.from_context(ctx, mesh=shard_mesh(devs)))
        with Session(ctx, engine, backend="serve", shards=shards,
                     telemetry=tel) as sess:
            rt = sess.backend.runtime
            for sh in rt.shards:
                sh.engine.telemetry = tel
                where = (sh.engine.mesh.devices.tolist()
                         if sh.engine.mesh is not None
                         else list(sh.engine.bsk_f.devices()))
                log(f"shards={shards}: shard {sh.index} keys on {where}")
            if shards == 4 and len({d for sh in rt.shards
                                    for d in sh.engine.bsk_f.devices()}) != 4:
                raise AssertionError("four shards are not on four devices")
            jobs = radix_jobs(sess, np.random.default_rng(ctx_key), 16)
            wall, outs = serve_wave(sess, jobs, jax.random.key(2000))
            counters = check_runtime(sess)
        results[shards] = [np.asarray(o).tolist() for o in outs]
        per_shard = {i: counters.get(f"serve.shard.{i}.completed", 0)
                     for i in range(shards)}
        log(f"shards={shards}: 16 radix clients decrypt to their oracles "
            f"in one wave of {wall:.3f} s (compile included); completed "
            f"per shard {per_shard}")
    if results[4] != results[1]:
        raise AssertionError("shards=4 and shards=1 decrypt differently")
    log("shards=4 decrypts are identical to shards=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-shard vs SPMD-mesh comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {jax.devices()}; there is "
              "no CPU fallback", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.runtime import compile_cache
    except ImportError as e:
        print(f"[chip_smoke] the repository's src/ is missing: {e}",
              file=sys.stderr)
        return 1
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"jax {jax.__version__}, libtpu {libtpu}, device_kind "
        f"{dev.device_kind!r}, {len(jax.devices())} device(s); compile "
        f"cache {compile_cache.enable()}")
    (four_chips if args.four_chips else one_chip)(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
