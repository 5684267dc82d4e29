"""repro.obs: metrics primitives, span tracing + Chrome export,
bandwidth ledger, serve-stack integration (concurrent-burst metric
consistency, per-output futures, stats-view compatibility), the
benchmark harness's exit-code contract, and the telemetry-off
overhead guard.

Key material comes from the session-scoped fixtures in conftest.py;
queue-level tests use linear-only (PBS-free) programs, and the one
PBS-heavy integration test shares a single small fused wave.
"""
import json
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from repro.compiler.ir import trace
from repro.core.integer import IntegerContext
from repro.obs import (BandwidthLedger, Histogram, MetricsRegistry,
                       StatsView, Telemetry, engine_key_bytes,
                       validate_chrome_trace)
from repro.runtime.fault import FaultConfig
from repro.serve import (ServeRuntime, decrypt_radix_output,
                         encrypt_request_inputs, radix_binop_program)

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # benchmarks/

BITS = 8


@pytest.fixture()
def ic4(ctx_4bit, engine_4bit):
    return IntegerContext.create(ctx_4bit, engine_4bit)


def _linear_graph(const):
    return trace(lambda x: x + np.array([const]), (1,))


# --- metrics primitives ------------------------------------------------------

def test_registry_counters_gauges_histograms_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("requests")
    assert reg.counter("requests") is c            # get-or-create
    c.inc()
    c.inc(4)
    reg.gauge("depth").set(7)
    h = reg.histogram("lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"] == {"requests": 5}
    assert snap["gauges"] == {"depth": 7.0}
    s = snap["histograms"]["lat"]
    assert s["count"] == 4 and s["sum"] == 10.0 and s["mean"] == 2.5
    assert s["min"] == 1.0 and s["max"] == 4.0 and s["p50"] == 3.0


def test_counter_concurrent_increments_exact():
    reg = MetricsRegistry()
    c = reg.counter("n")

    def worker():
        for _ in range(5_000):
            c.inc()

    ts = [threading.Thread(target=worker) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 40_000


def test_histogram_reservoir_past_cap_stays_calibrated():
    """count/sum/min/max are exact past the reservoir cap, and the
    sketch's quantiles track a known distribution (seeded RNG: exact
    reproducibility, no flake tolerance needed)."""
    h = Histogram("lat", max_samples=512)
    n = 10_000
    for i in range(n):
        h.observe(i / n)                    # uniform [0, 1)
    assert h.count == n
    assert h.total == pytest.approx(sum(i / n for i in range(n)))
    assert h.min == 0.0 and h.max == (n - 1) / n
    assert len(h._samples) == 512           # bounded memory
    assert h.quantile(0.50) == pytest.approx(0.5, abs=0.08)
    assert h.quantile(0.99) == pytest.approx(0.99, abs=0.08)


def test_stats_view_is_readonly_live_mapping():
    reg = MetricsRegistry()
    c = reg.counter("done")
    log = [("a", 0)]
    view = StatsView({"done": c, "rate": lambda: 0.5, "admitted": log})
    assert view["done"] == 0
    c.inc(3)
    assert view["done"] == 3                # live, not a copy
    assert view["rate"] == 0.5              # callables evaluated
    assert view["admitted"] is log          # logs pass through
    assert dict(view.as_dict()) == {"done": 3, "rate": 0.5, "admitted": log}
    with pytest.raises(TypeError):
        view["done"] = 9                    # Mapping, not MutableMapping


def test_telemetry_defaults_and_disabled():
    tel = Telemetry()                       # serve default: metrics only
    assert not tel.tracing
    tel.counter("c").inc()
    with tel.span("s", cat="t"):
        pass
    assert tel.snapshot()["counters"] == {"c": 1}
    assert tel.chrome_trace()["traceEvents"] == []   # tracing off

    off = Telemetry.disabled()
    off.counter("c").inc(100)
    off.histogram("h").observe(1.0)
    off.bandwidth.account_round(participants=2, rows_logical=1,
                                rows_dispatched=1, rows_padded=0,
                                bsk_bytes=10, ksk_bytes=10)
    snap = off.snapshot()
    assert snap["counters"] == {} and snap["bandwidth"] == {}


# --- span tracing + Chrome export -------------------------------------------

def test_trace_recorder_spans_instants_backfill_roundtrip(tmp_path):
    tel = Telemetry(trace=True)
    t0 = time.perf_counter()
    with tel.span("request", cat="serve", request=0) as sp:
        tel.instant("submit", cat="serve", request=0)
        with tel.span("pbs_round", cat="sched"):
            time.sleep(0.002)
        sp.set(outcome="completed")         # args discovered mid-span
    tel.record("queue_wait", "serve", t0 - 0.01, 0.005, request=0)

    spans = tel.recorder.spans()
    names = [s.name for s in spans]
    assert sorted(names) == ["pbs_round", "queue_wait", "request"]
    req = next(s for s in spans if s.name == "request")
    rnd = next(s for s in spans if s.name == "pbs_round")
    assert req.args == {"request": 0, "outcome": "completed"}
    assert req.ts <= rnd.ts and rnd.ts + rnd.dur <= req.ts + req.dur

    # exports validate: as an object, as a JSON string, and as a file
    obj = tel.chrome_trace()
    n = validate_chrome_trace(obj)
    assert n == validate_chrome_trace(json.dumps(obj))
    path = tel.write_chrome_trace(str(tmp_path / "t.json"))
    assert validate_chrome_trace(path) == n
    phs = [e["ph"] for e in obj["traceEvents"]]
    assert phs.count("X") == 3 and phs.count("i") == 1 and "M" in phs


def test_validate_chrome_trace_rejects_partial_overlap():
    def ev(name, ts, dur):
        return {"name": name, "ph": "X", "pid": 1, "tid": 0,
                "ts": ts, "dur": dur}

    ok = {"traceEvents": [ev("a", 0, 10), ev("b", 2, 5)]}       # nested
    assert validate_chrome_trace(ok) == 2
    bad = {"traceEvents": [ev("a", 0, 10), ev("b", 5, 10)]}     # partial
    with pytest.raises(ValueError, match="partially"):
        validate_chrome_trace(bad)
    with pytest.raises(ValueError, match="missing"):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "i"}]})


# --- bandwidth ledger --------------------------------------------------------

def test_bandwidth_ledger_counterfactual_math():
    led = BandwidthLedger()
    led.account_round(participants=4, rows_logical=16, rows_dispatched=12,
                      rows_padded=4, bsk_bytes=1000, ksk_bytes=100)
    led.account_round(participants=1, rows_logical=4, rows_dispatched=4,
                      rows_padded=0, bsk_bytes=1000, ksk_bytes=100)
    snap = led.snapshot()
    # each round streams the keys once; unfused would stream them
    # participants-many times — saved = sum (participants-1) * bytes
    assert snap["bsk_bytes_streamed"] == 2_000
    assert snap["bsk_bytes_unfused"] == 5_000
    assert snap["bsk_bytes_saved"] == 3_000 == led.bsk_bytes_saved
    assert snap["ksk_bytes_saved"] == 300
    assert snap["rows_deduped"] == 4        # dedup is rows, not key bytes
    assert snap["rows_padded"] == 4 and snap["fused_rounds"] == 2


# --- serve-stack integration -------------------------------------------------

def test_concurrent_burst_metrics_consistent(ctx_2bit, engine_2bit):
    """Multi-client burst with queueing and a poisoned client: every
    accounting surface must agree — spans vs counters vs histograms vs
    the stats view — and the trace must round-trip valid."""
    def chaos(request, attempt):
        if request.client_id == "poison":
            raise RuntimeError("poisoned request")

    tel = Telemetry(trace=True)
    rt = ServeRuntime(ctx_2bit, engine_2bit, fused=False, max_inflight=4,
                      fault=FaultConfig(max_retries=1), fault_hook=chaos,
                      start_paused=True, telemetry=tel)
    g = _linear_graph(1)
    x = ctx_2bit.encrypt(jax.random.key(8), np.array([1]))
    handles = []
    for i in range(12):                     # 4 clients x 3 requests
        handles.append(rt.submit(g, [x], client_id=f"c{i % 4}"))
    bad = [rt.submit(g, [x], client_id="poison") for _ in range(2)]
    rt.resume()
    rt.close()
    n_total = len(handles) + len(bad)

    snap = rt.metrics()
    c = snap["counters"]
    assert c["serve.admitted"] == n_total
    assert c["serve.completed"] + c["serve.failed"] == n_total
    assert c["serve.completed"] == len(handles)
    assert c["serve.failed"] == len(bad)
    assert c["serve.retries"] == len(bad)   # max_retries=1 -> 1 re-run each
    assert snap["histograms"]["serve.request_latency_s"]["count"] == n_total
    assert snap["histograms"]["serve.queue_wait_s"]["count"] == n_total
    assert snap["histograms"]["serve.queue_depth"]["max"] >= 4

    # the backward-compatible stats view reads the same registry
    assert rt.stats["completed"] == c["serve.completed"]
    assert rt.stats["failed"] == c["serve.failed"]
    assert len(rt.stats["admitted"]) == n_total

    # spans: one "request" span per admission, outcomes match counters
    events = tel.recorder.events()
    req_spans = [e for e in events if e.name == "request"]
    assert len(req_spans) == n_total
    outcomes = [e.args["outcome"] for e in req_spans]
    assert outcomes.count("completed") == c["serve.completed"]
    assert outcomes.count("failed") == c["serve.failed"]
    assert len([e for e in events if e.name == "submit"]) == n_total
    assert len([e for e in events if e.name == "queue_wait"]) == n_total
    retry_marks = [e for e in events if e.name == "retry"]
    assert len(retry_marks) == c["serve.retries"]

    # the trace round-trips through the Chrome exporter as valid JSON
    # with correctly nested spans on every lane
    assert validate_chrome_trace(json.dumps(tel.chrome_trace())) > 0

    for h in handles:
        assert int(ctx_2bit.decrypt(h.outputs()[0][0])) == 2


def test_output_futures_resolve_and_fail(ctx_2bit, engine_2bit):
    rt = ServeRuntime(ctx_2bit, engine_2bit, fused=False)
    g = _linear_graph(1)
    x = ctx_2bit.encrypt(jax.random.key(9), np.array([2]))
    h = rt.submit(g, [x], client_id="A")
    (fut,) = h.output_futures
    out = fut.wait(timeout=30)              # per-output completion handle
    assert fut.done() and fut.error is None
    assert int(ctx_2bit.decrypt(out[0])) == 3
    h.wait(timeout=30)
    # the future resolved during execution, not after the request closed
    assert fut.completed_at <= h.completed_at
    assert h.submitted_at <= h.admitted_at <= fut.completed_at
    # same ciphertext the handle-level API returns
    assert out is h.outputs()[0]

    def boom(request, attempt):
        raise RuntimeError("poisoned request")

    rt2 = ServeRuntime(ctx_2bit, engine_2bit, fused=False,
                       fault=FaultConfig(max_retries=1), fault_hook=boom)
    h2 = rt2.submit(g, [x], client_id="B")
    (fut2,) = h2.output_futures
    with pytest.raises(RuntimeError, match="poisoned"):
        fut2.wait(timeout=30)               # unresolved futures fail
    assert fut2.done() and fut2.completed_at is None
    rt.close()
    rt2.close()


def test_fused_wave_publishes_scheduler_and_bandwidth(ctx_4bit, engine_4bit,
                                                      ic4):
    """One small fused radix wave: scheduler counters agree between the
    stats view and the snapshot, pbs_round spans carry fused batch ids,
    and the bandwidth ledger's totals reconcile with the engine's actual
    key-material sizes."""
    m = ic4.spec(BITS).msg_bits
    g = radix_binop_program("radix_add", BITS, m)
    jobs = []
    for i, (a, b) in enumerate([(17, 201), (90, 90)]):
        enc = encrypt_request_inputs(ic4, jax.random.key(60 + i),
                                     [a, b], BITS)
        jobs.append((f"c{i}", enc, (a + b) % 256))
    jobs.append(("c2", jobs[0][1], jobs[0][2]))   # replayed ciphertexts
    tel = Telemetry(trace=True)
    rt = ServeRuntime(ctx_4bit, engine_4bit, max_inflight=len(jobs),
                      start_paused=True, telemetry=tel)
    handles = [rt.submit(g, enc, client_id=c) for c, enc, _ in jobs]
    rt.resume()
    rt.close()
    for h, (_, _, want) in zip(handles, jobs):
        assert decrypt_radix_output(ic4, h.outputs()[0], BITS)[0] == want

    snap = rt.metrics()
    c = snap["counters"]
    sv = rt.scheduler.stats
    for key in ("fused_rounds", "logical_luts", "dispatched_luts",
                "padded_luts", "dedup_hits"):
        assert sv[key] == c[f"sched.{key}"], key
    assert sv["dedup_hits"] > 0             # jobs[2] replays jobs[0]
    assert c["sched.fused_rounds"] > 0
    assert snap["histograms"]["sched.occupancy"]["count"] \
        == c["sched.fused_rounds"]
    # integer-layer accounting rode the same registry
    assert c["integer.pbs"] == c["sched.logical_luts"]

    # bandwidth: streamed == rounds * key bytes, unfused == participants *
    bsk_b, ksk_b = engine_key_bytes(engine_4bit)
    bw = snap["bandwidth"]
    assert bw["bsk_bytes_streamed"] == bw["fused_rounds"] * bsk_b
    assert bw["ksk_bytes_streamed"] == bw["fused_rounds"] * ksk_b
    assert bw["bsk_bytes_unfused"] == bw["participants"] * bsk_b
    assert bw["bsk_bytes_saved"] == bw["bsk_bytes_unfused"] \
        - bw["bsk_bytes_streamed"]
    assert bw["bsk_bytes_saved"] > 0        # every round fused 3 requests
    assert bw["rows_deduped"] == c["sched.dedup_hits"]

    # every pbs_round span landed a fused batch id; the leader's
    # fused_round spans nest inside its own pbs_round barrier wait
    events = tel.recorder.events()
    rounds = [e for e in events if e.name == "pbs_round"]
    assert len(rounds) == 3 * c["sched.fused_rounds"]   # one per request
    assert all(e.args.get("round") is not None for e in rounds)
    fused = [e for e in events if e.name == "fused_round"]
    assert len(fused) == c["sched.fused_rounds"]
    assert all(e.args["participants"] == len(jobs) for e in fused)
    assert validate_chrome_trace(json.dumps(tel.chrome_trace())) > 0


def test_noop_telemetry_overhead_under_5_percent(ctx_2bit, engine_2bit):
    """ISSUE acceptance: disabled telemetry must add <5% wall-clock to a
    fused serve pass.  Measured structurally, not as a timing diff (two
    serve waves on shared CPU differ by more than 5% from noise alone):
    count the telemetry touchpoints an actual wave makes, microbenchmark
    the per-touchpoint cost of the disabled primitives, and bound the
    product against the measured wave time."""
    tel = Telemetry()                       # metrics on, trace off
    rt = ServeRuntime(ctx_2bit, engine_2bit, fused=False, max_inflight=4,
                      start_paused=True, telemetry=tel)
    g = _linear_graph(1)
    x = ctx_2bit.encrypt(jax.random.key(12), np.array([1]))
    handles = [rt.submit(g, [x], client_id=f"c{i % 4}") for i in range(12)]
    t0 = time.perf_counter()
    rt.resume()
    rt.close()
    wave_s = time.perf_counter() - t0
    for h in handles:
        h.wait(timeout=30)

    snap = rt.metrics()
    # every counter inc, histogram observe, gauge set (2 per submit is an
    # overestimate), span/instant the wave performed
    n_requests = snap["counters"]["serve.admitted"]
    n_ops = (sum(snap["counters"].values())
             + sum(h["count"] for h in snap["histograms"].values())
             + 8 * n_requests)              # spans+instants+gauge, generous

    off = Telemetry.disabled()
    reps = 20_000
    t0 = time.perf_counter()
    for _ in range(reps):
        with off.span("s", cat="t", a=1):
            pass
        off.counter("c").inc()
        off.histogram("h").observe(1.0)
        off.instant("i", cat="t")
    per_op = (time.perf_counter() - t0) / (4 * reps)

    overhead_s = n_ops * per_op
    assert overhead_s < 0.05 * wave_s, (
        f"no-op telemetry cost {overhead_s * 1e3:.2f}ms over {n_ops} "
        f"touchpoints vs wave {wave_s * 1e3:.0f}ms")


# --- benchmark harness exit-code contract ------------------------------------

def _bench_main(argv, mods):
    from benchmarks.run import main
    return main(argv, mods=mods)


def test_bench_run_exits_nonzero_on_failure(tmp_path, capsys):
    ok = SimpleNamespace(run=lambda: [{"bench": "x", "v": 1}])

    def explode():
        raise RuntimeError("bench blew up")

    bad = SimpleNamespace(run=explode)
    rc = _bench_main(["--only", "ok,bad", "--out-dir", str(tmp_path)],
                     {"ok": ok, "bad": bad})
    assert rc == 1                          # a partial run is a red run
    rows = json.loads((tmp_path / "results.json").read_text())
    assert rows == [{"bench": "x", "v": 1}]    # surviving rows kept
    assert "bad" in capsys.readouterr().out
    rc = _bench_main(["--only", "ok", "--out-dir", str(tmp_path)],
                     {"ok": ok, "bad": bad})
    assert rc == 0
    assert _bench_main(["--only", "nope"], {"ok": ok}) == 2


def test_bench_dry_run_checks_obs_columns():
    scaling = ("shards", "clients", "requests_per_s",
               "per_shard_occupancy", "occupancy_ratio")
    good = SimpleNamespace(
        run=lambda: [],
        BENCH_COLUMNS=("p50_s", "p99_s", "bsk_bytes_saved", "extra"),
        SCALING_COLUMNS=scaling)
    assert _bench_main(["--only", "serve", "--dry-run"],
                       {"serve": good}) == 0
    # a serve benchmark that stops declaring the observability columns
    # must fail the dry run (BENCH_serve.json consumers key on them)
    stale = SimpleNamespace(run=lambda: [], BENCH_COLUMNS=("p50_s",),
                            SCALING_COLUMNS=scaling)
    assert _bench_main(["--only", "serve", "--dry-run"],
                       {"serve": stale}) == 1
    # likewise for the shard-sweep scaling row's columns (PR 10)
    noscale = SimpleNamespace(run=lambda: [],
                              BENCH_COLUMNS=good.BENCH_COLUMNS,
                              SCALING_COLUMNS=("shards",))
    assert _bench_main(["--only", "serve", "--dry-run"],
                       {"serve": noscale}) == 1
    norun = SimpleNamespace(BENCH_COLUMNS=good.BENCH_COLUMNS,
                            SCALING_COLUMNS=scaling)
    assert _bench_main(["--only", "serve", "--dry-run"],
                       {"serve": norun}) == 1


def test_bench_dry_run_real_modules_pass():
    """The real harness dry-run (entry points + obs columns + trace
    exporter) stays green — this is what the CI smoke lane executes."""
    from benchmarks.run import main
    assert main(["--dry-run", "--only", "serve,fhe_ml"]) == 0


# --- Snapshot.diff (PR 8 satellite: phase-windowed metric deltas) -----------

def test_snapshot_diff_counters_gauges_and_exact_interval_quantiles():
    reg = MetricsRegistry()
    c = reg.counter("serve.completed")
    g = reg.gauge("serve.queue_depth")
    h = reg.histogram("serve.request_latency_s")
    c.inc(3)
    g.set(5)
    for v in (10.0, 20.0):
        h.observe(v)
    earlier = reg.snapshot()
    c.inc(4)
    g.set(2)
    for v in (30.0, 40.0, 50.0, 60.0):
        h.observe(v)
    later = reg.snapshot()
    delta = later.diff(earlier)
    # counters subtract, gauges report the later value
    assert delta["counters"]["serve.completed"] == 4
    assert delta["gauges"]["serve.queue_depth"] == 2
    # the histogram window covers ONLY the interval's samples, exactly
    hd = delta["histograms"]["serve.request_latency_s"]
    assert hd["count"] == 4 and hd["sum"] == 180.0 and hd["mean"] == 45.0
    assert hd["min"] == 30.0 and hd["max"] == 60.0
    assert hd["p50"] == 50.0 and hd["p99"] == 60.0
    # instruments created after `earlier` diff against zero
    reg.counter("serve.abandoned").inc(2)
    delta2 = reg.snapshot().diff(earlier)
    assert delta2["counters"]["serve.abandoned"] == 2
    # an empty interval has count 0 and None quantiles
    empty = reg.snapshot().diff(reg.snapshot())
    hd0 = empty["histograms"]["serve.request_latency_s"]
    assert hd0["count"] == 0 and hd0["p50"] is None
    # diffs are JSON-clean (what BENCH_sim.json consumers see)
    json.dumps(delta)


def test_snapshot_diff_reservoir_fallback_keeps_exact_counts():
    """Past the sample cap the interval quantiles are no longer exact —
    diff() must degrade to exact count/sum/mean with None quantiles
    rather than report wrong tails."""
    reg = MetricsRegistry()
    h = reg.histogram("lat", 64)
    for v in range(10):
        h.observe(float(v))
    earlier = reg.snapshot()
    for v in range(100):                      # blows past the cap of 64
        h.observe(float(v))
    delta = reg.snapshot().diff(earlier)
    hd = delta["histograms"]["lat"]
    assert hd["count"] == 100
    assert hd["sum"] == float(sum(range(100)))
    assert hd["p50"] is None and hd["p99"] is None


def test_snapshot_diff_bandwidth_and_telemetry_roundtrip():
    tel = Telemetry()
    tel.counter("serve.admitted").inc(2)
    tel.bandwidth.account_round(participants=2, rows_logical=4,
                                rows_dispatched=3, rows_padded=1,
                                bsk_bytes=1000, ksk_bytes=100)
    earlier = tel.snapshot()
    tel.counter("serve.admitted").inc(5)
    tel.bandwidth.account_round(participants=3, rows_logical=6,
                                rows_dispatched=5, rows_padded=0,
                                bsk_bytes=1000, ksk_bytes=100)
    delta = tel.snapshot().diff(earlier)
    assert delta["counters"]["serve.admitted"] == 5
    # bandwidth ledger totals subtract like counters: only the second
    # round's traffic shows in the window
    assert delta["bandwidth"]["fused_rounds"] == 1
    assert delta["bandwidth"]["participants"] == 3
    assert delta["bandwidth"]["rows_dispatched"] == 5
    assert delta["bandwidth"]["bsk_bytes_streamed"] == 1000
    assert delta["bandwidth"]["bsk_bytes_unfused"] == 3000
    json.dumps(delta)


# --- the round's timeline: ids, causes, engine room, profiler, compiles ------

def _lanes(events):
    by: dict = {}
    for e in events:
        if e.dur is not None:
            by.setdefault(e.tid, []).append(e)
    return by


@pytest.fixture(scope="module")
def traced_fanout(ctx_4bit):
    """ONE traced request whose radix add fans out over two digit
    vectors, on a private engine whose three engine-room entries count
    their calls."""
    import jax.numpy as jnp

    from repro.core.engine import TaurusEngine

    engine = TaurusEngine.from_context(ctx_4bit)
    calls = {"lut_batch": 0, "lut_batch_small": 0, "keyswitch": 0}
    for name in calls:
        real = getattr(engine, name)

        def counted(*a, _real=real, _name=name, **kw):
            calls[_name] += 1
            return _real(*a, **kw)
        setattr(engine, name, counted)
    ic = IntegerContext.create(ctx_4bit, engine)
    m, d = ic.spec(BITS).msg_bits, ic.spec(BITS).n_digits
    g = trace(lambda a, b: a.radix_add(b, msg_bits=m), (2, d), (2, d))
    xs, ys = [17, 250], [90, 9]
    enc = [jnp.concatenate(encrypt_request_inputs(
               ic, jax.random.key(70 + j), vals, BITS))
           for j, vals in enumerate((xs, ys))]
    tel = Telemetry(trace=True)
    rt = ServeRuntime(ctx_4bit, engine, max_inflight=1, start_paused=True,
                      telemetry=tel)
    h = rt.submit(g, enc, client_id="A")
    rt.resume()
    rt.close()
    got = decrypt_radix_output(ic, h.outputs()[0], BITS)
    assert got == [(x + y) % 256 for x, y in zip(xs, ys)]
    return SimpleNamespace(tel=tel, rt=rt, rid=h.request.request_id,
                           calls=calls, events=tel.recorder.events())


def test_span_ids_parents_and_request_follow_the_radix_fanout(
        traced_fanout):
    events, rid = traced_fanout.events, traced_fanout.rid
    by_id = {e.id: e for e in events}
    assert len(by_id) == len(events)                  # ids are unique
    req = next(e for e in events if e.name == "request")
    assert req.args["request"] == rid and req.parent is None
    fan = [e for e in events if e.tid != req.tid and e.dur is not None
           and e.name in ("radix_linear", "lut_encode", "await_rows",
                          "row_keys", "pbs_round", "barrier_wait",
                          "fused_round")]
    assert {e.tid for e in fan}, "no spans on the fan-out threads"
    assert len({e.tid for e in fan}) == 2             # two digit vectors
    for e in fan:
        # every span of the fan-out names the request and descends from
        # its span, through the adopted cause
        assert e.args["request"] == rid, e
        p = e
        while p.parent is not None:
            p = by_id[p.parent]
        assert p is req, e
    # the outermost span of each fan-out thread has the request span as
    # its parent: the cause crossed the thread boundary
    for tid in {e.tid for e in fan}:
        first = min((e for e in fan if e.tid == tid), key=lambda e: e.ts)
        assert by_id[first.parent].tid == req.tid
    # round ids link each pbs_round to one fused_round, and each
    # engine_room to the fused_round that enqueued it (its parent)
    fused = {e.args["round"]: e for e in events if e.name == "fused_round"}
    for e in events:
        if e.name == "pbs_round":
            assert e.args["round"] in fused
        if e.name == "engine_room":
            assert by_id[e.parent] is fused[e.args["round"]]
    # the Chrome export carries them
    x = [ev for ev in traced_fanout.tel.chrome_trace()["traceEvents"]
         if ev["ph"] == "X"]
    assert all("id" in ev["args"] and "parent" in ev["args"] for ev in x)


def test_one_engine_room_span_per_engine_room_call(traced_fanout):
    events, calls = traced_fanout.events, traced_fanout.calls
    rooms = [e for e in events if e.name == "engine_room"]
    assert len(rooms) == sum(calls.values()) > 0
    progs = [e.args["program"] for e in rooms]
    assert progs.count("keyswitch_batch_jit") == calls["keyswitch"]
    assert progs.count("pbs_batch_small") == calls["lut_batch_small"]
    assert progs.count("pbs_batch") == calls["lut_batch"]
    # the spans of the device's watcher lane never overlap, and they are
    # what the scheduler dispatched, row for row
    assert {e.thread for e in rooms} == {"engine-room cpu:0"}
    rooms.sort(key=lambda e: e.ts)
    for a, b in zip(rooms, rooms[1:]):
        assert b.ts >= a.ts + a.dur
    assert all(e.dur > 0 and e.args["queued_ms"] >= 0 for e in rooms)
    c = traced_fanout.rt.metrics()["counters"]
    assert sum(e.args["padded"] for e in rooms
               if e.args["program"].startswith("pbs_batch")) \
        == c["sched.padded_luts"]
    assert sum(e.args["rows"] for e in rooms
               if e.args["program"].startswith("pbs_batch")) \
        == c["sched.dispatched_luts"]
    assert validate_chrome_trace(traced_fanout.tel.chrome_trace()) > 0


def test_engine_room_spans_name_the_key_layout(traced_fanout):
    """Each execution names the BSK layout it ran against: f64 planes on
    the CPU (int8 blocks on a TPU)."""
    rooms = [e for e in traced_fanout.events if e.name == "engine_room"]
    assert rooms and {e.args["layout"] for e in rooms} == {"f64_planes"}


def test_tracing_off_starts_no_watcher(ctx_2bit, engine_2bit):
    mod = ctx_2bit.params.plaintext_modulus
    table = np.array([(3 * v + 1) % mod for v in range(mod)])
    g = trace(lambda x: (x + np.array([1, 0, 1, 0])).lut(table), (4,))
    x = ctx_2bit.encrypt(jax.random.key(5), np.array([0, 1, 2, 1]))

    def wave(tel):
        before = set(threading.enumerate())
        rt = ServeRuntime(ctx_2bit, engine_2bit, max_inflight=1,
                          telemetry=tel)
        h = rt.submit(g, [x], client_id="A")
        jax.block_until_ready(h.outputs())
        rt.close()
        return [t for t in set(threading.enumerate()) - before
                if t.name.startswith("engine-room")]

    off = Telemetry()
    assert wave(off) == []
    assert off.recorder.events() == []
    assert off.snapshot()["counters"]["sched.fused_rounds"] > 0
    on = Telemetry(trace=True)
    assert wave(on)                     # the same wave, traced, starts one
    names = {e.name for e in on.recorder.events()}
    assert {"radix_linear", "lut_encode", "engine_room"} <= names


def test_spans_sit_on_the_profiles_host_plane(tmp_path):
    """Each span() also enters a TraceAnnotation: a JAX profile taken on
    the CPU holds the program's span names on its host plane, and a
    backfilled record() does not."""
    import glob

    tel = Telemetry(trace=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("probe_outer", cat="t"):
            with tel.span("probe_inner", cat="t"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
        tel.record("probe_backfill", "t", time.perf_counter() - 1e-3, 1e-3)
        with Telemetry().span("probe_untraced"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    pd = jax.profiler.ProfileData.from_file(path)
    host = {ev.name for plane in pd.planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events}
    assert {"probe_outer", "probe_inner"} <= host
    assert "probe_backfill" not in host and "probe_untraced" not in host


def test_forced_compile_counts_and_records_a_compile_span():
    tel = Telemetry(trace=True)
    assert tel.snapshot()["counters"]["jit.compiles"] == 0
    salt = time.perf_counter_ns() % 1_000_003        # a program never seen
    with tel.span("outer", cat="t", request=7) as sp:
        jax.jit(lambda v: v * 3 + salt)(jax.numpy.arange(5))
    c = tel.snapshot()["counters"]
    assert c["jit.compiles"] >= 1
    comp = [e for e in tel.recorder.spans() if e.name == "compile"]
    assert len(comp) == c["jit.compiles"]
    outer = next(e for e in tel.recorder.spans() if e.name == "outer")
    for e in comp:
        # on the compiling thread, inside the span that compiled
        assert e.tid == outer.tid and e.parent == sp.id
        assert e.args["request"] == 7
        assert outer.ts <= e.ts and e.ts + e.dur <= outer.ts + outer.dur


def test_watcher_records_every_execution_under_contention(monkeypatch):
    """Many threads hand executions to one recorder's watcher at once,
    with a short thread switch interval and a watcher thread that exits
    when idle for 1 ms and starts again: every execution is recorded
    once, and the device lane's spans never overlap."""
    from repro.obs import watch_execution, watcher

    monkeypatch.setattr(watcher, "LINGER_S", 0.001)
    tel = Telemetry(trace=True)
    n_threads, per_thread = 16, 25

    def work(i):
        for j in range(per_thread):
            with tel.span("fused_round", cat="sched", round=i * 100 + j):
                out = jax.numpy.full((4,), i * 100 + j)
                watch_execution(out, program="p", rows=4, padded=4)
            if j % 5 == 0:
                time.sleep(0.003)              # let the watcher go idle

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        rooms = [e for e in tel.recorder.spans() if e.name == "engine_room"]
    finally:
        sys.setswitchinterval(old)
    assert len(rooms) == n_threads * per_thread
    assert sorted(e.args["round"] for e in rooms) == sorted(
        i * 100 + j for i in range(n_threads) for j in range(per_thread))
    rooms.sort(key=lambda e: e.ts)
    for a, b in zip(rooms, rooms[1:]):
        assert b.ts >= a.ts + a.dur
    assert validate_chrome_trace(tel.chrome_trace()) > 0
