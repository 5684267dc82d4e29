"""Trace a mixed serving run and write Chrome-trace JSON.

    PYTHONPATH=src python examples/trace_serve.py [--out trace_serve.json]

Two radix-add clients and one encrypted-GPT-2-block client (the
quantize-to-radix lowering from `repro.fhe_ml`) run concurrently
through `ServeRuntime` with a tracing `Telemetry` attached.  Every
layer records spans: per-request `submit -> queue_wait -> admit ->
pbs_round (fused batch id, dedup hits) -> completed`, the host steps
between rounds (`radix_linear`, `lut_encode`, `await_rows`,
`row_keys`, `barrier_wait`), the scheduler's leader-side `fused_round`
dispatches, every compile, and each engine-room execution's device
time (`engine_room`, on a lane of its own).  The script writes the
trace, validates it (JSON shape, span nesting, per-request coverage),
and prints the metrics snapshot headlines — open the file at
https://ui.perfetto.dev or chrome://tracing to see the fleet's rounds
barrier into shared batches.

The CI smoke lane runs this end-to-end and uploads the trace as a
workflow artifact.
"""
from __future__ import annotations

import argparse
import sys
import time

BITS = 16
MSG_BITS = 2
D_MODEL = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="trace_serve.json",
                    help="Chrome-trace output path")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from repro.api import IntSpec, Session
    from repro.core.engine import TaurusEngine
    from repro.core.params import TEST_PARAMS_4BIT
    from repro.core.pbs import TFHEContext
    from repro.fhe_ml import lower
    from repro.fhe_ml.quantize import calibrate_radix, quantize_to_radix
    from repro.obs import Telemetry, validate_chrome_trace

    params = TEST_PARAMS_4BIT
    ctx = TFHEContext.create(jax.random.PRNGKey(0), params)
    engine = TaurusEngine.from_context(ctx)
    tel = Telemetry(trace=True)

    client = Session(ctx, engine, backend="local")
    add_prog = client.trace(lambda a, b: a + b, IntSpec(BITS), IntSpec(BITS))
    g, meta = lower.lower_gpt2_block_radix(D_MODEL, bits=BITS,
                                           msg_bits=MSG_BITS, seed=1)
    block_prog = client.compile(g, meta["in_specs"], meta["out_specs"])

    rng = np.random.default_rng(3)
    reqs = []                        # (client, program, enc_inputs, want)
    for i, name in enumerate(("alice", "bob")):
        a = int(rng.integers(0, 1 << BITS))
        b = int(rng.integers(0, 1 << BITS))
        enc = client.encrypt_inputs(jax.random.key(10 + i), [a, b], add_prog)
        reqs.append((name, add_prog, enc, (a + b) % (1 << BITS)))
    xf = rng.uniform(-1, 1, D_MODEL)
    rq = calibrate_radix(xf, BITS, MSG_BITS, qmax=meta["input_qmax"])
    q = quantize_to_radix(xf, rq)
    enc = client.encrypt_inputs(jax.random.key(99), [q], block_prog)
    reqs.append(("carol", block_prog, enc, meta["int_fn"](q) % (1 << BITS)))

    print(f"== traced serving run: 2 radix-add + 1 GPT-2-block clients "
          f"({BITS}-bit radix, {params.name}) ==")
    sess = Session(ctx, engine, backend="serve", telemetry=tel,
                   max_inflight=len(reqs), start_paused=True)
    handles = [sess.submit(p, e, client_id=c) for c, p, e, _ in reqs]
    rt = sess.backend.runtime
    t0 = time.perf_counter()
    rt.resume()
    rt.drain()
    dt = time.perf_counter() - t0
    for h, (c, p, _, want) in zip(handles, reqs):
        got = np.asarray(sess.decrypt_outputs(p, h.outputs())[0])
        assert np.array_equal(got % (1 << BITS), want), f"{c}: FHE != oracle"
    sess.close()

    path = tel.write_chrome_trace(args.out)
    n_events = validate_chrome_trace(path)

    # per-request coverage: a submit instant, the request span, at least
    # one pbs_round span nested inside it (same worker lane), a complete
    # marker — the trace is only useful if every request's whole journey
    # is on it
    events = tel.recorder.events()
    for h in handles:
        rid = h.request.request_id
        mine = [e for e in events if e.args.get("request") == rid]
        names = {e.name for e in mine}
        for needed in ("submit", "admit", "queue_wait", "request",
                       "completed"):
            assert needed in names, f"request {rid} missing {needed!r} event"
        req_span = next(e for e in mine if e.name == "request")
        rounds = [e for e in events
                  if e.name == "pbs_round" and e.tid == req_span.tid
                  and e.ts >= req_span.ts
                  and e.ts + e.dur <= req_span.ts + req_span.dur]
        assert rounds, f"request {rid}: no pbs_round span inside its span"
        assert all(r.args.get("round") is not None for r in rounds), (
            f"request {rid}: pbs_round missing its fused batch id")

    snap = rt.metrics()
    lat = snap["histograms"]["serve.request_latency_s"]
    bw = snap["bandwidth"]
    occ = snap["histograms"]["sched.occupancy"]
    print(f"   {len(reqs)} requests in {dt:5.1f}s "
          f"(includes XLA compilation of the block's shapes)")
    print(f"   latency p50 {lat['p50']:.2f}s p99 {lat['p99']:.2f}s; "
          f"{snap['counters']['sched.fused_rounds']} fused rounds, "
          f"mean occupancy {occ['mean']:.0%}")
    print(f"   BSK streamed {bw['bsk_bytes_streamed'] / 1e6:.1f} MB vs "
          f"{bw['bsk_bytes_unfused'] / 1e6:.1f} MB unfused "
          f"(saved {bw['bsk_bytes_saved'] / 1e6:.1f} MB)")
    rooms = [e for e in events if e.name == "engine_room"]
    print(f"   {len(rooms)} engine-room executions, "
          f"{sum(e.dur for e in rooms):.1f}s busy; "
          f"{snap['counters']['jit.compiles']} compiles")
    print(f"[trace_serve] {n_events} events -> {path} "
          f"(open in https://ui.perfetto.dev)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
