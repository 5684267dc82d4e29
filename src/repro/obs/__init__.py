"""repro.obs — end-to-end tracing, metrics, and bandwidth accounting
for the FHE serving stack.

One `Telemetry` object threads through every layer of the serve path:

  metrics    typed counters/gauges/latency histograms in a
             `MetricsRegistry` (p50/p95/p99 from streaming quantile
             sketches), published by `ServeRuntime`,
             `FusedLutScheduler`, `IntegerContext` and JAX's own
             compile events (`jit.*`); read through one `snapshot()`
             (also `ServeRuntime.metrics()`).
  tracing    request spans — submit -> queue-wait -> admit -> per-PBS-
             round (fused batch id, occupancy, dedup hits) ->
             complete/retry/fail — the host steps between rounds, each
             engine-room execution's device time (`engine_room`) and
             every compile, via a lock-cheap per-thread
             `TraceRecorder`; each span has an id and a parent, and
             also enters a `jax.profiler.TraceAnnotation`, so a JAX
             profile shows it beside the device's ops.  Exportable as
             Chrome-trace JSON (Perfetto / chrome://tracing) or
             inspected in-memory.
  bandwidth  a `BandwidthLedger` accounting BSK/KSK bytes streamed per
             fused round vs. the unfused counterfactual — the paper's
             key-reuse saving as a measured quantity
             (`bsk_bytes_saved` in BENCH_serve.json).

Tracing is DISABLED by default: `Telemetry()` keeps the metrics
registry live (it replaced the serve layer's ad-hoc stats dicts) but
hands out a no-op recorder, so the hot path pays ~nothing when nobody
is looking.  `Telemetry(trace=True)` turns the recorder on;
`Telemetry.disabled()` is the fully inert twin (no-op metrics too).

    from repro.obs import Telemetry

    tel = Telemetry(trace=True)
    rt = ServeRuntime(ctx, telemetry=tel)          # or Session(..., telemetry=tel)
    ...serve traffic...
    snap = rt.metrics()                            # == tel.snapshot()
    tel.write_chrome_trace("trace.json")           # open in Perfetto

See docs/ARCHITECTURE.md ("Observability") for the span model and the
metrics catalog; `examples/trace_serve.py` writes a real trace from a
mixed radix + GPT-2-block serving run.
"""
from __future__ import annotations

import threading
import time
import weakref

from repro.obs.bandwidth import (NULL_LEDGER, BandwidthLedger, NullLedger,
                                 engine_key_bytes)
from repro.obs.metrics import (NULL_REGISTRY, Counter, Gauge, Histogram,
                               MetricsRegistry, NullRegistry, Snapshot,
                               StatsView)
from repro.obs.trace import (NOOP_RECORDER, NoopRecorder, SpanEvent,
                             TraceRecorder, adopt, current_span,
                             validate_chrome_trace, watch_execution)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "jit.cache_hits",
                 "/jax/compilation_cache/cache_misses": "jit.cache_misses"}

# every live Telemetry hears JAX's compile events through ONE listener
# pair, registered with jax.monitoring on the first Telemetry
_live: "weakref.WeakSet" = weakref.WeakSet()
_live_lock = threading.Lock()
_listening = False


def _listen(tel: "Telemetry") -> None:
    global _listening
    with _live_lock:
        _live.add(tel)
        if not _listening:
            import jax.monitoring as mon
            mon.register_event_duration_secs_listener(_on_duration)
            mon.register_event_listener(_on_event)
            _listening = True


def _live_telemetry() -> list:
    with _live_lock:
        return list(_live)


def _on_duration(name: str, secs: float, **_) -> None:
    """A backend compile: counted, and a `compile` span [now - secs,
    now] on the compiling thread."""
    if name != _COMPILE_EVENT:
        return
    now = time.perf_counter()
    for tel in _live_telemetry():
        tel.counter("jit.compiles").inc()
        tel.record("compile", "jax", now - secs, secs)


def _on_event(name: str, **_) -> None:
    counter = _CACHE_EVENTS.get(name)
    if counter is not None:
        for tel in _live_telemetry():
            tel.counter(counter).inc()


class Telemetry:
    """The one telemetry handle every serve-path layer accepts.

    trace:   record spans (default False — no-op recorder).
    metrics: keep a live registry + bandwidth ledger (default True).
    """

    def __init__(self, *, trace: bool = False, metrics: bool = True):
        self.registry = MetricsRegistry() if metrics else NULL_REGISTRY
        self.recorder = TraceRecorder() if trace else NOOP_RECORDER
        self.bandwidth = BandwidthLedger() if metrics else NULL_LEDGER
        if trace:
            # a traced window reads its jit.* counters as 0, not absent,
            # when nothing compiled in it
            for name in ("jit.compiles", *_CACHE_EVENTS.values()):
                self.counter(name)
        _listen(self)

    @classmethod
    def disabled(cls) -> "Telemetry":
        """Fully inert telemetry: every instrument is a shared no-op."""
        return cls(trace=False, metrics=False)

    @property
    def tracing(self) -> bool:
        return self.recorder.enabled

    # -- tracing -------------------------------------------------------------
    def span(self, name: str, cat: str = "serve", **args):
        return self.recorder.span(name, cat, **args)

    def instant(self, name: str, cat: str = "serve", **args) -> None:
        self.recorder.instant(name, cat, **args)

    def record(self, name: str, cat: str, ts: float, dur: float,
               **args) -> None:
        self.recorder.record(name, cat, ts, dur, **args)

    def chrome_trace(self) -> dict:
        return self.recorder.chrome_trace()

    def write_chrome_trace(self, path: str) -> str:
        import json
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    # -- metrics -------------------------------------------------------------
    def counter(self, name: str) -> Counter:
        return self.registry.counter(name)

    def gauge(self, name: str) -> Gauge:
        return self.registry.gauge(name)

    def histogram(self, name: str, max_samples: int = 4096) -> Histogram:
        return self.registry.histogram(name, max_samples)

    def snapshot(self) -> Snapshot:
        """The single structured view: registry instruments plus the
        bandwidth ledger.  A `Snapshot`, so two phase-boundary calls
        diff into a windowed delta: ``later.diff(earlier)``."""
        snap = self.registry.snapshot()
        snap["bandwidth"] = self.bandwidth.snapshot()
        return snap


__all__ = [
    "BandwidthLedger", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NOOP_RECORDER", "NULL_LEDGER", "NULL_REGISTRY", "NoopRecorder",
    "NullLedger", "NullRegistry", "Snapshot", "SpanEvent", "StatsView",
    "Telemetry", "TraceRecorder", "adopt", "current_span",
    "engine_key_bytes", "validate_chrome_trace", "watch_execution",
]
