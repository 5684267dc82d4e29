#!/usr/bin/env python3
"""The chip benchmark: one cell per process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell is one configuration under one traffic mix, both named in
`BENCHMARK.json` (see `cells.py`).  One run:

  1. set-up (reported as `setup_s`, from process start to window start):
     secret keys from the seed, evaluation keys on the chip in one jitted
     call, the programs traced once, every program shape the cell's fused
     rounds can reach compiled (or loaded from the persistent compilation
     cache), a short warm-up of the serving path, and every request's
     inputs encrypted in one call, each digit with its own randomness;
  2. the window: `--seconds` of traffic through
     `Session(ctx, backend="serve").submit`, open loop (requests due at
     fixed times, latency counted from the due time) or closed loop
     (clients that send their next request when the last is answered);
     with `--trace 1` its first `TRACE_S` seconds under the JAX profiler;
  3. the check, after the window and outside every timing: every answer
     decrypted with the benchmark's own secret key and compared with the
     plain reference (`programs/<family>.py`);
  4. one JSON line on standard output: `correct`, `attempted`, `failed`,
     `metrics` (the cell's end-to-end metrics, or with `--trace 1` its
     per-layer metrics), `device`, with `--trace 1` `breakdown`, and last
     `checks`, each number compared beside its limit.  The same numbers
     close standard error.

There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import cells  # noqa: E402
import client  # noqa: E402
import loadgen  # noqa: E402

REPO = cells.REPO
OUT = os.path.join(HERE, ".out")
WARM_SEED = 7          # warm-up requests are the same in every run
DRAIN_S = 60.0         # how long past the close an answer may come
TRACE_S = 4.0          # how much of the window `--trace 1` traces: a
                       # longer trace loses device events


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def require_chips(n: int):
    """The TPU devices of the run; raises NoChip without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs}; there is no CPU fallback")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def params_of(cfg: dict):
    from repro.core.params import TFHEParams
    p = TFHEParams(
        name=cfg["params_name"], n=cfg["n"], N=cfg["N"], k=cfg["k"],
        width=cfg["width"], pbs_base_log=cfg["pbs_base_log"],
        pbs_level=cfg["pbs_level"], ks_base_log=cfg["ks_base_log"],
        ks_level=cfg["ks_level"], lwe_std=cfg["lwe_std"],
        glwe_std=cfg["glwe_std"],
        padding_bits=cfg["padding_bits"])
    p.validate()
    return p


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

class CompileLog:
    """Backend compiles and persistent-cache hits and misses seen by JAX,
    with their times."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.events = []
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name == self.COMPILE:
            self.events.append((time.perf_counter(), name, secs))

    def _on_event(self, name, **_):
        if name in (self.HIT, self.MISS):
            self.events.append((time.perf_counter(), name, 0.0))

    def between(self, t0: float, t1: float) -> tuple:
        """(compiles, their seconds, cache hits, cache misses) in [t0, t1]."""
        ev = [(n, s) for t, n, s in self.events if t0 <= t <= t1]
        comp = [s for n, s in ev if n == self.COMPILE]
        return (len(comp), sum(comp), sum(n == self.HIT for n, _ in ev),
                sum(n == self.MISS for n, _ in ev))

    def since(self, t0: float) -> str:
        n, s, hits, misses = self.between(t0, time.perf_counter())
        return (f"{n} compiles ({s:.3f} s), {hits} cache hits, "
                f"{misses} misses")


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def device_peak(device) -> dict:
    """The device's row of `peaks.json`; a device not in the table is an
    error, never a default."""
    peaks = cells.load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device.device_kind not in peaks:
        raise KeyError(f"device {device.device_kind!r} is not in peaks.json")
    return peaks[device.device_kind]


@dataclasses.dataclass
class Server:
    cell: cells.Cell
    params: object
    ctx: object
    programs: dict
    widths: dict
    devices: list
    peak: dict


def _round_shapes(spans) -> list:
    """(rows dispatched, rows keyswitched) of every fused round."""
    out = []
    for s in spans:
        if s.name == "fused_round":
            n = int(s.args.get("dispatched", s.args.get("rows", 0)))
            out.append((n, n - int(s.args.get("ks_dedup_hits", 0)),
                        int(s.args.get("ks_dedup_hits", 0)) > 0))
    return out


def cell_inflight(cell: cells.Cell) -> int:
    """How many requests the cell's traffic can have in flight at once:
    its clients in a closed loop, at most the server's `max_inflight`."""
    cap = int(cell.config["server"]["max_inflight"])
    if cell.traffic["arrivals"]["kind"] == "closed":
        return min(cap, int(cell.traffic["clients"]))
    return cap


def reachable_widths(rounds: list, inflight: int) -> dict:
    """The padded batch widths each engine-room program can be called with
    when `inflight` requests fuse their rounds, from the rounds one request
    of each operation makes alone (`_round_shapes`)."""
    from repro.core.integer import _pad_batch
    rows_all = max(r for r, _, _ in rounds)
    rows_plain = max([r for r, _, split in rounds if not split] or [0])
    keyswitched = max(k for _, k, _ in rounds)
    upto = lambda m: sorted({_pad_batch(b) for b in range(1, m + 1)})
    return {"pbs_batch": upto(inflight * rows_plain),
            "pbs_batch_small": upto(inflight * rows_all),
            "keyswitch_batch_jit": upto(inflight * keyswitched)}


def compile_widths(ctx, p, widths: dict, workers: int = 8) -> int:
    """Compile (or load from the persistent cache) each engine-room
    program at each width, without running it, several at a time."""
    import concurrent.futures
    import jax
    import jax.numpy as jnp
    from repro.core import batch
    u64 = jnp.uint64
    ct = lambda w: jax.ShapeDtypeStruct((w, p.big_n + 1), u64)
    small = lambda w: jax.ShapeDtypeStruct((w, p.n + 1), u64)
    poly = lambda w: jax.ShapeDtypeStruct((w, p.N), u64)
    jobs = [lambda w=w: batch.pbs_batch.lower(
                ct(w), poly(w), ctx.bsk_f, ctx.ksk, params=p).compile()
            for w in widths["pbs_batch"]]
    jobs += [lambda w=w: batch.pbs_batch_small.lower(
                 small(w), poly(w), ctx.bsk_f, params=p).compile()
             for w in widths["pbs_batch_small"]]
    jobs += [lambda w=w: batch.keyswitch_batch_jit.lower(
                 ct(w), ctx.ksk, params=p).compile()
             for w in widths["keyswitch_batch_jit"]]
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        for f in [pool.submit(j) for j in jobs]:
            f.result()
    return len(jobs)


def make_session(server: Server, telemetry):
    from repro.api import Session
    srv = server.cell.config["server"]
    return Session(server.ctx, backend="serve", telemetry=telemetry,
                   shards=srv["shards"], max_inflight=srv["max_inflight"])


def setup(cell: cells.Cell, seed: int, devices,
          compiles: CompileLog) -> Server:
    import jax
    from repro.obs import Telemetry

    cfg = cell.config
    p = params_of(cfg)
    peak = device_peak(devices[0])
    t = time.perf_counter()
    ctx = client.make_context(seed, p)
    jax.block_until_ready((ctx.bsk_f, ctx.ksk))
    log(f"evaluation keys {p.name}: {time.perf_counter() - t:.3f} s; "
        f"{compiles.since(t)}")

    # one request of each operation alone: the shapes of its rounds
    tel = Telemetry(trace=True)
    server = Server(cell, p, ctx, {}, {}, list(devices), peak)
    sess = make_session(server, tel)
    try:
        server.programs = cell.family.build(sess, cfg)
        t = time.perf_counter()
        warm = warm_requests(server, list(server.programs), WARM_SEED)
        for r in warm:
            run_blocking(sess, server.programs[r.op], r.enc)
        rounds = _round_shapes(tel.recorder.spans())
        log(f"warm-up: {len(warm)} lone requests: "
            f"{time.perf_counter() - t:.3f} s; {compiles.since(t)}")
        t = time.perf_counter()
        server.widths = reachable_widths(rounds, cell_inflight(cell))
        n = compile_widths(ctx, p, server.widths)
        log(f"warm-up: {n} engine-room programs {json.dumps(server.widths)}"
            f": {time.perf_counter() - t:.3f} s; {compiles.since(t)}")
    finally:
        sess.close()
    # the cell's own traffic for `warm_s` seconds, the same in every run:
    # the host-side shapes of its fused rounds
    warm_s = float(cell.traffic.get("warm_s", 0))
    if warm_s > 0:
        t = time.perf_counter()
        plan = loadgen.make_plan(cell.traffic, cell.family.ARITY,
                                 cfg["bits"], WARM_SEED, warm_s)
        sess = make_session(server, Telemetry())
        try:
            drive(sess, server, plan, warm_s)
        finally:
            sess.backend.runtime.close(drain=False)
        log(f"warm-up: {warm_s} s of the cell's traffic: "
            f"{time.perf_counter() - t:.3f} s; {compiles.since(t)}")
    return server


def run_blocking(sess, prog, enc):
    import jax
    return jax.block_until_ready(sess.submit(prog, enc).outputs())


@dataclasses.dataclass
class Request:
    op: str
    args: list
    client: int = 0
    due: float | None = None        # seconds after the window opens
    enc: list | None = None
    due_abs: float | None = None    # perf_counter stamps
    sent: float | None = None
    ready: float | None = None
    handle: object = None
    outputs: list | None = None
    error: str | None = None

    @property
    def latency(self) -> float:
        start = self.due_abs if self.due_abs is not None else self.sent
        return self.ready - start if self.ready is not None else math.inf


def encrypt_requests(server: Server, reqs: list, seed: int) -> None:
    """Every input of every request encrypted in one call, each digit with
    its own randomness; fills `r.enc`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg = server.cell.config
    fam = server.cell.family
    rows = [fam.digits(a, cfg["bits"], cfg["msg_bits"])
            for r in reqs for a in r.args]
    key = jax.random.key(loadgen.seeded_rng("inputs", seed).getrandbits(31))
    enc = client.encryptor(server.params)(key, jnp.asarray(np.stack(rows)),
                                          server.ctx.big_sk)
    jax.block_until_ready(enc)
    i = 0
    for r in reqs:
        r.enc = [enc[i + j] for j in range(len(r.args))]
        i += len(r.args)
    jax.block_until_ready([r.enc for r in reqs])


def warm_requests(server: Server, ops: list, seed: int) -> list:
    rng = loadgen.seeded_rng("warm-up", seed)
    bits = server.cell.config["bits"]
    reqs = [Request(op, [rng.getrandbits(bits)
                         for _ in range(server.cell.family.ARITY[op])])
            for op in ops]
    encrypt_requests(server, reqs, seed)
    return reqs


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    """What a reader of `metrics/` gets: the run's requests and stamps,
    the program's counters over the window, its spans, and the reduced
    device trace (None without `--trace 1`)."""
    open_loop: bool
    seconds: float
    t0: float
    t1: float
    requests: list
    counters: dict
    spans: list
    trace: dict | None
    params: object
    peak: dict
    setup_s: float
    generator_late_s: float
    compiles: tuple

    def in_window(self) -> list:
        """Requests due (open loop) or sent (closed loop) in the window."""
        return [r for r in self.requests if r.sent is not None and
                (r.due_abs if self.open_loop else r.sent) < self.t1]


def _watch(reqs: list, stop: threading.Event, deadline: float) -> None:
    """Stamp each open-loop request when its outputs are on the device;
    after `stop`, until every sent request is answered or `deadline`."""
    import jax
    while True:
        pending = [r for r in reqs if r.handle is not None
                   and r.ready is None and r.error is None]
        for r in pending:
            if r.handle.done():
                try:
                    r.outputs = jax.block_until_ready(r.handle.outputs())
                    r.ready = time.perf_counter()
                except Exception as e:  # noqa: BLE001 — recorded, checked
                    r.error = repr(e)
        if stop.is_set() and (time.perf_counter() > deadline or not any(
                r.ready is None and r.error is None
                for r in reqs if r.handle is not None)):
            return
        time.sleep(0.002)


class Tracer:
    """The profiler over the window's first `TRACE_S` seconds, marked by
    the annotation `bench.traced`; stopped by `poll` from the thread that
    started it, once that time has passed.  The stop blocks that thread
    for up to minutes on a v5e while the device trace is collected; the
    clients' threads go on meanwhile."""

    def __init__(self, on: bool, trace_dir: str | None):
        self.on, self.dir, self.stop_at = on, trace_dir, None
        self.stop_s = 0.0

    def start(self, t0: float, seconds: float) -> None:
        import jax
        if not self.on:
            return
        self.stop_at = t0 + min(seconds, TRACE_S)
        self.ann = jax.profiler.TraceAnnotation("bench.traced")
        self.ann.__enter__()

    def poll(self) -> None:
        import jax
        t = time.perf_counter()
        if self.stop_at is not None and t >= self.stop_at:
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.stop_at = None
            self.stop_s = time.perf_counter() - t


def drive(sess, server: Server, plan: loadgen.Plan, seconds: float,
          tracer: Tracer | None = None):
    """Send the plan's traffic; returns (requests, anchor, t0, t1,
    counters at t0, counters at t1, generator lateness)."""
    import jax
    ann = jax.profiler.TraceAnnotation
    tracer = tracer or Tracer(False, None)
    progs = server.programs
    tel = sess.telemetry
    reqs = [Request(r.op, r.args, r.client, r.due) for r in plan.requests]
    encrypt_requests(server, reqs, plan.seed)
    late = [0.0]

    def submit(r):
        r.sent = time.perf_counter()
        with ann("bench.submit"):
            r.handle = sess.submit(progs[r.op], r.enc,
                                   client_id=f"c{r.client}")

    anchor = time.perf_counter()
    with ann("bench.anchor"):
        pass
    t0 = time.perf_counter()
    snap0 = tel.snapshot()
    t1 = t0 + seconds
    # the counters at the close, from a timer: a profiler stop can hold
    # this thread past it
    snaps = []
    at_close = threading.Timer(t1 - time.perf_counter(),
                               lambda: snaps.append(tel.snapshot()))
    at_close.start()
    tracer.start(t0, seconds)
    if plan.open_loop:
        stop = threading.Event()
        watcher = threading.Thread(target=_watch,
                                   args=(reqs, stop, t1 + DRAIN_S),
                                   name="bench-watch")
        watcher.start()
        for r in reqs:
            r.due_abs = t0 + r.due
            _sleep_until(r.due_abs, tracer)
            submit(r)
            late[0] = max(late[0], r.sent - r.due_abs)
        _sleep_until(t1, tracer)
    else:
        queues = [[] for _ in range(plan.clients)]
        for r in reqs:
            queues[r.client].append(r)
        exhausted = []

        def client_loop(queue):
            for r in queue:
                if time.perf_counter() >= t1:
                    return
                submit(r)
                try:
                    with ann("bench.wait"):
                        outs = r.handle.wait(t1 + DRAIN_S
                                             - time.perf_counter())
                        r.outputs = jax.block_until_ready(
                            [outs[i] for i in
                             r.handle.request.graph.outputs])
                    r.ready = time.perf_counter()
                except Exception as e:  # noqa: BLE001 — checked later
                    r.error = repr(e)
                if plan.think_s:
                    time.sleep(plan.think_s)
            if time.perf_counter() < t1:
                exhausted.append(queue[0].client)

        threads = [threading.Thread(target=client_loop, args=(q,),
                                    name=f"bench-client-{i}")
                   for i, q in enumerate(queues)]
        for th in threads:
            th.start()
        _sleep_until(t1, tracer)
    tracer.poll()
    at_close.join()
    snap1 = snaps[0]
    if plan.open_loop:
        stop.set()
        watcher.join()
        for r in reqs:
            if r.sent is not None and r.ready is None and r.error is None:
                r.error = "no answer within the drain limit"
    else:
        for th in threads:
            th.join(max(0.0, t1 + DRAIN_S - time.perf_counter()) + 5)
        if exhausted:
            raise RuntimeError(
                f"clients {sorted(set(exhausted))} ran out of requests "
                f"before the window closed: raise pool_per_client")
    return reqs, anchor, t0, t1, snap0, snap1, late[0]


def _sleep_until(t: float, tracer: Tracer) -> None:
    while True:
        tracer.poll()
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check(server: Server, reqs: list, limits: dict) -> dict:
    """Decrypt every answer with the benchmark's own key and compare it
    with the plain reference.  Returns {name: (value, limit)}."""
    import numpy as np
    cfg, fam, p = server.cell.config, server.cell.family, server.params
    big_sk = np.asarray(server.ctx.big_sk)
    missing = wrong = 0
    worst = 0.0
    for r in reqs:
        if r.sent is None:
            continue
        if r.outputs is None:
            missing += 1
            continue
        want = fam.reference(r.op, r.args, cfg["bits"])
        ph = client.phases(np.asarray(r.outputs[0]), big_sk)
        got = fam.from_digits(client.decode(ph, p), cfg["msg_bits"],
                              cfg["bits"])
        wrong += got != want
        share = client.noise_share(
            ph, fam.digits(want, cfg["bits"], cfg["msg_bits"]), p)
        worst = max(worst, float(share.max()))
    return {"missing": (missing, 0), "wrong": (wrong, 0),
            "noise_share": (worst, limits["noise_share"])}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def measure(server: Server, plan: loadgen.Plan, seconds: float,
            tracing: bool, compiles: CompileLog) -> tuple:
    """One window on a set-up server; returns (Window, checks)."""
    import jax
    import trace_reduce
    from repro.obs import Telemetry

    tel = Telemetry(trace=tracing)
    sess = make_session(server, tel)
    tracer = Tracer(tracing, os.path.join(OUT, f"trace-{os.getpid()}"))
    try:
        if tracing:
            shutil.rmtree(tracer.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tracer.dir, profiler_options=opts)
        setup_s = time.perf_counter() - T_START
        reqs, anchor, t0, t1, snap0, snap1, late = drive(
            sess, server, plan, seconds, tracer)
        peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in server.devices)
    finally:
        sess.backend.runtime.close(drain=False)
    counters = snap1.diff(snap0)["counters"]
    spans = [s for s in tel.recorder.spans() if t0 <= s.ts < t1] \
        if tracing else []
    summary = None
    if tracing:
        t = time.perf_counter()
        pd = trace_reduce.load(tracer.dir)
        summary = trace_reduce.reduce(
            pd, [(s.name, s.ts, s.dur) for s in tel.recorder.spans()],
            anchor_pc=anchor)
        log(f"trace: stopped in {tracer.stop_s:.3f} s, read in "
            f"{time.perf_counter() - t:.3f} s")
        shutil.rmtree(tracer.dir, ignore_errors=True)
    win = Window(plan.open_loop, seconds, t0, t1, reqs, counters, spans,
                 summary, server.params, server.peak, setup_s, late,
                 compiles.between(t0, t1))
    win.memory_peak_bytes = peak_mem
    checks = check(server, reqs, server.cell.config["check"])
    return win, checks


def result_line(cell: cells.Cell, win: Window, checks: dict,
                tracing: bool) -> dict:
    metrics = {}
    for m in (cell.per_layer if tracing else cell.end_to_end):
        v = m.reader.read(win)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    devs = win_devices(cell)
    inw = win.in_window()
    failed = sum(1 for r in inw if r.outputs is None)
    correct = (len(inw) > 0 and all(v <= lim for v, lim in checks.values()))
    out = {"correct": bool(correct), "attempted": len(inw),
           "failed": failed + checks["wrong"][0], "metrics": metrics,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs),
                      "memory_peak_bytes": int(win.memory_peak_bytes)}}
    if tracing:
        out["device"]["busy_s"] = win.trace["busy_s"]
        out["device"]["window_s"] = win.trace["window_s"]
        out["breakdown"] = {"device_ops": win.trace["device_ops"],
                            "idle_gaps": win.trace["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def cache_mib(path: str) -> float:
    """Size of the persistent compilation cache directory."""
    total = 0
    for d, _, files in os.walk(path):
        total += sum(os.path.getsize(os.path.join(d, f)) for f in files)
    return total / 2**20


def win_devices(cell):
    import jax
    return jax.devices()[:cell.chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.runtime import compile_cache
    except ImportError as e:
        print(f"[bench] the program (src/repro) is missing: {e}",
              file=sys.stderr)
        return 2
    result = run_cell(cell, devices, args.seed, args.seconds,
                      bool(args.trace), compile_cache)
    print(json.dumps(result))
    return 0


def run_cell(cell, devices, seed: int, seconds: float, tracing: bool,
             compile_cache) -> dict:
    import jax
    cache = compile_cache.enable()
    cap = jax.config.jax_compilation_cache_max_size
    # every program of the run persists, the eager ones of the serving
    # path too, which compile in well under the default second; and none
    # is evicted: under a cap of 192 MiB this cell's programs pushed one
    # another out, and every run compiled its engine room again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    dev = devices[0]
    log(f"{cell.name} seed {seed}: {len(devices)} x {dev.device_kind} "
        f"({dev.platform}), jax {jax.__version__}, compile cache {cache} "
        f"(size cap {cap} lifted)")
    compiles = CompileLog()
    server = setup(cell, seed, devices, compiles)
    plan = loadgen.make_plan(cell.traffic, cell.family.ARITY,
                             cell.config["bits"], seed, seconds)
    win, checks = measure(server, plan, seconds, tracing, compiles)
    n, secs, hits, misses = win.compiles
    rounds = win.counters.get("sched.fused_rounds", 0)
    log(f"set-up {win.setup_s:.3f} s; window {seconds} s; "
        f"{len(win.in_window())} requests and {rounds} fused rounds in the "
        f"window ({1000 * seconds / max(rounds, 1):.1f} ms a round); "
        f"generator late by up to {win.generator_late_s:.4f} s; in the "
        f"window {n} compiles ({secs:.3f} s), {hits} cache hits, {misses} "
        f"misses; compile cache {cache_mib(cache):.1f} MiB")
    result = result_line(cell, win, checks, tracing)
    for k, (v, lim) in checks.items():
        log(f"check {k} {v} limit {lim}")
    return result


if __name__ == "__main__":
    sys.exit(main())
