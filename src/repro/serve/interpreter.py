"""IR interpreter: executes compiled `repro.compiler.ir` graphs on real
ciphertexts through an engine's batched PBS entry point.

This is the serving-side execution contract the compiler lowers to.  It
differs from `repro.api.EagerBackend` in two ways that matter for a
multi-tenant runtime:

  * every bootstrap goes through `engine.lut_batch` — hand it a
    `FusedEngineProxy` and all of a request's PBS rounds fuse with every
    other in-flight request's rounds (cross-request key reuse + dedup).
    In the sharded runtime (ISSUE 10) that proxy is
    `EngineShard.worker_engine()`: the interpreter is the execution
    body of ONE shard's worker, its rounds barrier only with requests
    the router placed on the same shard, and the proxy's KS-level dedup
    shares keyswitches between rows that differ only in table;
  * a tensor-level radix node over V > 1 digit vectors FLATTENS into V
    per-vector round streams executed on concurrent worker threads, each
    registered with the shared `FusedLutScheduler` — so the vectors of
    ONE request fuse with each other (intra-request fusion) exactly the
    way concurrent requests already do, and the scheduler's dedup/
    padding applies unchanged (ROADMAP serve-layer follow-up).

A radix node's tensor has its digit vector on the LAST axis; each
vector executes through `IntegerContext`
(`repro.api.backends.eval_radix_vector`, shared with the eager backend
so the radix semantics has one definition).

Tracing: the interpreter's host work between two rounds (linear nodes,
node bookkeeping, the radix layer's digit arithmetic) is one
`radix_linear` span per stretch, LUT encoding is `lut_encode`, and the
fan-out threads adopt the calling thread's open span, so their spans
name the request too.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.backends import eval_linear_ct_op, eval_radix_vector
from repro.compiler.ir import Graph, RADIX_OPS
from repro.core import glwe
from repro.core.engine import TaurusEngine
from repro.core.integer import IntegerContext
from repro.obs import NOOP_RECORDER, adopt, current_span


class IrInterpreter:
    """Runs a compiled Graph on real ciphertexts via `engine.lut_batch`.

    `engine` is a TaurusEngine or a `FusedEngineProxy`; with a proxy,
    per-round padding is left to the fused scheduler (padding tiny
    per-request rounds would only dilute the fused batch).

    intra_fuse: with a fused engine, execute the V vectors of one
    tensor-level radix node on V concurrent threads (each holding its
    own scheduler registration) so their identical round schedules
    barrier into shared batches.

    holds_slot: True when the calling thread itself holds a scheduler
    registration (a `ServeRuntime` worker) — the vector fan-out then
    parks that slot while it joins, so the barrier never waits on a
    thread that is not computing rounds.

    Example (the in-process serving contract, no queue)::

        interp = IrInterpreter(ctx, engine)
        outs = interp.run_outputs(program.graph, enc_inputs)
    """

    def __init__(self, ctx, engine=None, *,
                 pad_rounds: Optional[bool] = None,
                 intra_fuse: bool = True,
                 holds_slot: bool = False,
                 telemetry=None):
        self.ctx = ctx
        self.engine = engine if engine is not None \
            else TaurusEngine.from_context(ctx)
        self.params = ctx.params
        if pad_rounds is None:
            pad_rounds = not getattr(self.engine, "fused", False)
        self.telemetry = telemetry
        self._rec = (telemetry.recorder if telemetry is not None
                     else NOOP_RECORDER)
        self.int_ctx = IntegerContext(ctx, self.engine,
                                      pad_batches=pad_rounds,
                                      telemetry=telemetry)
        self.intra_fuse = intra_fuse
        self.holds_slot = holds_slot
        self._poly_cache: dict = {}

    # -- helpers -------------------------------------------------------------
    def _lut_poly(self, table: np.ndarray) -> jax.Array:
        key = np.ascontiguousarray(table).tobytes()
        if key not in self._poly_cache:
            with self._rec.span("lut_encode", cat="radix"):
                self._poly_cache[key] = glwe.make_lut_polys_cached(
                    np.asarray(table)[None], self.params)[0]
        return self._poly_cache[key]

    # upper bound on fan-out threads per radix node: beyond this, each
    # worker takes a contiguous slice of vectors sequentially (rounds
    # still fuse MAX_FANOUT wide; unbounded V-wide threading would risk
    # thread exhaustion and stack churn on large tensors)
    MAX_FANOUT = 32

    def _radix_fanout(self, n, spec, a: jax.Array,
                      b: Optional[jax.Array], sched,
                      max_val: Optional[int] = None) -> list:
        """Per-vector rounds on concurrent threads sharing `sched`: the
        scheduler barrier fuses them like independent requests."""
        V = int(a.shape[0])
        outs: list = [None] * V
        errors: list = []
        nt = min(V, self.MAX_FANOUT)
        slices = [range(w, V, nt) for w in range(nt)]
        cause = current_span()

        def work(idx) -> None:
            try:
                with adopt(cause), self.int_ctx.linear_stretch():
                    for v in idx:
                        outs[v] = eval_radix_vector(
                            self.int_ctx, n.op, spec, a[v],
                            None if b is None else b[v], max_val=max_val)
            except BaseException as err:  # noqa: BLE001 — re-raised below
                errors.append(err)
            finally:
                sched.unregister()

        threads = [threading.Thread(target=work, args=(idx,), daemon=True)
                   for idx in slices]
        # register every worker BEFORE any starts so the barrier width is
        # right from the first round; a started thread owns its slot (the
        # finally above releases it), slots of never-started threads are
        # released here so a start() failure can't inflate the barrier
        # forever
        for _ in threads:
            sched.register()
        started = 0
        try:
            for t in threads:
                t.start()
                started += 1
        finally:
            for _ in range(len(threads) - started):
                sched.unregister()
            # park the request's own slot while joining (this thread
            # computes no rounds meanwhile)
            if self.holds_slot:
                sched.unregister()
            try:
                for t in threads[:started]:
                    t.join()
            finally:
                if self.holds_slot:
                    sched.register()
        if errors:
            raise errors[0]
        return outs

    def _radix(self, n, vals) -> jax.Array:
        m, d = n.attrs["msg_bits"], n.attrs["n_digits"]
        ic = self.int_ctx
        spec = ic.spec(m * d, m)
        width = self.params.big_n + 1
        a = vals[n.inputs[0]].reshape(-1, d, width)
        b, mv = None, None
        if n.op == "radix_linear":
            # LPU combine + carry-save compress on the request thread (the
            # extraction rounds batch across ALL output columns, and still
            # fuse with other in-flight requests through the proxy); only
            # the final per-vector propagation fans out below
            a, mv = ic.linear_compress(a, n.attrs["W"], spec)
        elif n.op == "radix_norm":
            mv = n.attrs["max_val"]
        elif len(n.inputs) == 2:
            b = vals[n.inputs[1]].reshape(-1, d, width)
        sched = getattr(self.engine, "_scheduler", None)
        if self.intra_fuse and sched is not None and a.shape[0] > 1:
            with ic.cut_stretch():
                outs = self._radix_fanout(n, spec, a, b, sched, max_val=mv)
        else:
            outs = [eval_radix_vector(ic, n.op, spec, a[v],
                                      None if b is None else b[v],
                                      max_val=mv)
                    for v in range(a.shape[0])]
        return jnp.concatenate(outs, axis=0)

    # -- run ------------------------------------------------------------------
    def run(self, g: Graph, enc_inputs: list,
            on_node=None) -> dict:
        """enc_inputs: one (n_elements, k*N+1) ciphertext array per input
        node.  Returns {node_id: ciphertext array} for every node.

        on_node: optional callback `on_node(node_id, value)` fired the
        moment each node's value materializes — `ServeRuntime` resolves
        per-output futures through it, so a request's early outputs are
        readable while later nodes still execute."""
        vals: dict = {}
        it = iter(enc_inputs)
        ic = self.int_ctx
        with ic.linear_stretch():
            for n in g.nodes:
                if n.op == "input":
                    vals[n.id] = next(it)
                else:
                    out = eval_linear_ct_op(n, vals, self.params)
                    if out is not None:
                        vals[n.id] = out
                    elif n.op == "lut":
                        cts = vals[n.inputs[0]]
                        poly = self._lut_poly(n.attrs["table"])
                        polys = jnp.broadcast_to(
                            poly, (cts.shape[0],) + poly.shape)
                        with ic.cut_stretch():
                            vals[n.id] = self.engine.lut_batch(cts, polys)
                    elif n.op in RADIX_OPS:
                        vals[n.id] = self._radix(n, vals)
                    else:
                        raise ValueError(n.op)
                if on_node is not None:
                    on_node(n.id, vals[n.id])
        return vals

    def run_outputs(self, g: Graph, enc_inputs: list) -> list:
        """Like `run`, but returns just the graph outputs, in order."""
        vals = self.run(g, enc_inputs)
        return [vals[i] for i in g.outputs]
