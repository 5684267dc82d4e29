"""Pluggable execution backends behind the `Session` front door.

One protocol — `Backend.execute(program, enc_inputs) -> outputs` — three
implementations:

  EagerBackend   direct execution for debugging: `lut` nodes run the
                 KS-first PBS pipeline with the paper's KS/ACC dedup
                 live (the former `fhe_ml.FheExecutor` engine room,
                 moved here; `FheExecutor.run` is now a shim), radix
                 nodes run straight through `IntegerContext`.
  LocalBackend   the serving execution contract in-process:
                 `repro.serve.IrInterpreter`, every bootstrap through
                 `engine.lut_batch`; `fused=True` wraps the engine in a
                 private `FusedLutScheduler` so one request's
                 multi-vector radix rounds fuse intra-request.
  ServeBackend   submits through the multi-tenant `ServeRuntime` and
                 wraps its `RequestHandle` — the same program joins
                 cross-request round fusion and online dedup.

`repro.serve` imports stay lazy (function-local): this module is
imported by `repro.fhe_ml.executor` and by `repro.serve` itself, and
the linear-op evaluator below is the single definition both executors
share.
"""
from __future__ import annotations

from typing import Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import glwe, lwe, torus
from repro.core import batch as batch_mod
from repro.core.integer import IntegerContext, RadixCiphertext
from repro.core.params import TFHEParams

U64 = jnp.uint64


@runtime_checkable
class Backend(Protocol):
    """The execution contract every backend implements."""
    name: str

    def execute(self, program, enc_inputs: list) -> list:
        """Run `program.graph` on encrypted inputs; returns the output
        ciphertext arrays in `program.graph.outputs` order."""
        ...


# ---------------------------------------------------------------------------
# shared node evaluators
# ---------------------------------------------------------------------------

def eval_linear_ct_op(n, vals: dict, p: TFHEParams):
    """Evaluate one PBS-free IR node on ciphertext tensors (LPU work:
    add/sub/addc/mulc/linear/reshape/concat).  Returns the result array,
    or None if `n` is not a linear op.  Shared by `EagerBackend` and
    `repro.serve.IrInterpreter` so their linear semantics cannot
    diverge."""
    delta = p.delta
    if n.op == "add":
        return lwe.add(vals[n.inputs[0]], vals[n.inputs[1]])
    if n.op == "sub":
        return lwe.sub(vals[n.inputs[0]], vals[n.inputs[1]])
    if n.op == "addc":
        c = torus.encode(jnp.asarray(
            np.asarray(n.attrs["const"], np.int64).reshape(-1)
            % (1 << p.width), dtype=U64), delta)
        x = vals[n.inputs[0]]
        c = jnp.broadcast_to(c, x.shape[:-1])
        return x.at[..., -1].add(c)
    if n.op == "mulc":
        c = np.asarray(n.attrs["const"], np.int64).reshape(-1)
        return vals[n.inputs[0]] * jnp.asarray(
            c, jnp.int64)[:, None].astype(U64)
    if n.op == "linear":
        W = jnp.asarray(np.asarray(n.attrs["W"], np.int64))
        x = vals[n.inputs[0]]                      # (in, big_n+1)
        # wraparound multiply-reduce, not a dot: no uint64 dot on TPU
        y = jnp.sum(W.astype(U64)[:, :, None] * x[:, None, :], axis=0,
                    dtype=U64)
        if n.attrs.get("bias") is not None:
            b = torus.encode(jnp.asarray(
                np.asarray(n.attrs["bias"], np.int64).reshape(-1)
                % (1 << p.width), U64), delta)
            y = y.at[..., -1].add(b)
        return y
    if n.op == "radix_addc":
        # digitize the constant and add each digit onto the matching
        # digit ciphertext's body — LPU only, result left un-propagated
        # (its digit ceiling rides on the node's max_val attr)
        m, d = n.attrs["msg_bits"], n.attrs["n_digits"]
        c = int(n.attrs["const"]) % (1 << (m * d))
        digs = np.array([(c >> (i * m)) & ((1 << m) - 1) for i in range(d)],
                        dtype=np.uint64)
        x = vals[n.inputs[0]]                      # (V*d, big_n+1)
        enc = torus.encode(jnp.asarray(np.tile(digs, x.shape[0] // d)),
                           delta)
        return x.at[..., -1].add(enc)
    if n.op == "radix_mulc":
        return lwe.scalar_mul(vals[n.inputs[0]], int(n.attrs["const"]))
    if n.op in ("reshape", "concat"):
        return vals[n.inputs[0]]
    return None


def eval_radix_vector(ic: IntegerContext, op: str, spec, av: jax.Array,
                      bv: Optional[jax.Array],
                      max_val: Optional[int] = None) -> jax.Array:
    """One radix IR op on ONE digit vector through `IntegerContext`.
    Shared by `EagerBackend` and `repro.serve.IrInterpreter` — the
    radix execution semantics has exactly one definition.

    For `radix_linear`, `av` is one PRE-COMBINED output vector from
    `IntegerContext.linear_compress` and `max_val` its digit ceiling;
    this evaluator finishes the carry propagation (so the per-vector
    propagation rounds fan out / fuse exactly like the elementwise
    radix ops)."""
    ra = RadixCiphertext(spec, av)
    if op in ("radix_linear", "radix_norm"):
        return ic.propagate(ra, max_val=max_val).digits
    if op == "radix_add":
        return ic.add(ra, RadixCiphertext(spec, bv)).digits
    if op == "radix_sub":
        return ic.sub(ra, RadixCiphertext(spec, bv)).digits
    if op == "radix_mul":
        return ic.mul(ra, RadixCiphertext(spec, bv)).digits
    if op == "radix_relu":
        return ic.relu_clamp(ra).digits
    if op == "radix_cmp":
        return ic.compare(ra, RadixCiphertext(spec, bv))[None]
    raise ValueError(op)


# ---------------------------------------------------------------------------
# eager
# ---------------------------------------------------------------------------

class EagerBackend:
    """Direct execution for debugging: no queue, no round scheduler.

    `lut` nodes run the KS-first PBS pipeline with both paper dedups
    live (KS results cached per source tensor, one accumulator image per
    unique table); `radix_*` nodes dispatch per digit vector through a
    private `IntegerContext`.  Counts what it does in `stats`.
    """

    name = "eager"

    def __init__(self, ctx, engine=None, *, ks_dedup: bool = True,
                 acc_dedup: bool = True, pad_batches: bool = True,
                 telemetry=None):
        from repro.core.engine import TaurusEngine
        self.ctx = ctx
        self.params: TFHEParams = ctx.params
        self.ks_dedup = ks_dedup
        self.acc_dedup = acc_dedup
        self.telemetry = telemetry
        self.int_ctx = IntegerContext.create(
            ctx, engine or TaurusEngine.from_context(ctx),
            pad_batches=pad_batches, telemetry=telemetry)
        self.stats = {"pbs": 0, "keyswitch": 0, "lut_polys": 0}
        self._lut_cache: dict = {}

    # -- the KS-first PBS pipeline (per unique-table accumulator) -----------
    def _lut_poly(self, table: np.ndarray):
        key = table.tobytes() if self.acc_dedup else object()
        if key not in self._lut_cache:
            self._lut_cache[key] = glwe.make_lut_poly(
                jnp.asarray(table, U64), self.params)
            self.stats["lut_polys"] += 1
        return self._lut_cache[key]

    def _pbs(self, cts, table, small_cache_key, ks_cache):
        """PBS with the KS-first order so key-switch results are reusable."""
        p = self.params
        if self.ks_dedup and small_cache_key in ks_cache:
            small = ks_cache[small_cache_key]
        else:
            small = batch_mod.keyswitch_batch(cts, self.ctx.ksk, p)
            self.stats["keyswitch"] += int(cts.shape[0])
            ks_cache[small_cache_key] = small
        ms = lwe.mod_switch(small, p.log2_N + 1)
        poly = self._lut_poly(table)
        luts = glwe.trivial(jnp.broadcast_to(poly, (cts.shape[0], p.N)), p.k)
        acc = batch_mod.blind_rotate_batch(luts, ms, self.ctx.bsk_f, p)
        self.stats["pbs"] += int(cts.shape[0])
        return glwe.sample_extract(acc)

    def _radix(self, n, vals: dict) -> jax.Array:
        m, d = n.attrs["msg_bits"], n.attrs["n_digits"]
        spec = self.int_ctx.spec(m * d, m)
        width = self.params.big_n + 1
        a = vals[n.inputs[0]].reshape(-1, d, width)
        b, mv = None, None
        if n.op == "radix_linear":
            # LPU-combine + carry-save compress to one vector per output
            # column; the per-vector loop below finishes the propagation
            a, mv = self.int_ctx.linear_compress(a, n.attrs["W"], spec)
        elif n.op == "radix_norm":
            mv = n.attrs["max_val"]
        elif len(n.inputs) == 2:
            b = vals[n.inputs[1]].reshape(-1, d, width)
        outs = [eval_radix_vector(self.int_ctx, n.op, spec, a[v],
                                  None if b is None else b[v], max_val=mv)
                for v in range(a.shape[0])]
        return jnp.concatenate(outs, axis=0)

    # -- run -----------------------------------------------------------------
    def run(self, g, enc_inputs: list) -> dict:
        """Execute a Graph; returns {node_id: ciphertext array} for every
        node (the historical `FheExecutor.run` contract)."""
        from repro.compiler.ir import RADIX_OPS
        vals: dict = {}
        ks_cache: dict = {}
        it = iter(enc_inputs)
        for n in g.nodes:
            if n.op == "input":
                vals[n.id] = next(it)
                continue
            out = eval_linear_ct_op(n, vals, self.params)
            if out is not None:
                vals[n.id] = out
            elif n.op == "lut":
                vals[n.id] = self._pbs(vals[n.inputs[0]],
                                       np.asarray(n.attrs["table"]),
                                       n.inputs[0], ks_cache)
            elif n.op in RADIX_OPS:
                vals[n.id] = self._radix(n, vals)
            else:
                raise ValueError(n.op)
        return vals

    def execute(self, program, enc_inputs: list) -> list:
        vals = self.run(program.graph, enc_inputs)
        return [vals[o] for o in program.graph.outputs]


# ---------------------------------------------------------------------------
# local (serving interpreter in-process)
# ---------------------------------------------------------------------------

class LocalBackend:
    """The serving execution contract without the queue: a
    `repro.serve.IrInterpreter` over this process's engine.  With
    `fused=True` the engine is wrapped in a private `FusedLutScheduler`,
    so the per-vector rounds of one program's tensor-level radix nodes
    fuse into shared batches (intra-request fusion, no runtime needed).
    """

    name = "local"

    def __init__(self, ctx, engine=None, *, fused: bool = False,
                 telemetry=None):
        from repro.core.engine import TaurusEngine
        from repro.serve.interpreter import IrInterpreter
        from repro.serve.scheduler import FusedLutScheduler
        engine = engine or TaurusEngine.from_context(ctx)
        self.telemetry = telemetry
        self.scheduler = (FusedLutScheduler(telemetry=telemetry)
                          if fused else None)
        eng = self.scheduler.proxy(engine) if fused else engine
        self.interp = IrInterpreter(ctx, eng, telemetry=telemetry)

    def execute(self, program, enc_inputs: list) -> list:
        return self.interp.run_outputs(program.graph, enc_inputs)


# ---------------------------------------------------------------------------
# serve (multi-tenant runtime)
# ---------------------------------------------------------------------------

class ServeBackend:
    """Submits programs through a `ServeRuntime`: the session's traffic
    joins cross-request fused PBS rounds and online dedup, and a traced
    program's tensor-level radix nodes flatten into per-vector rounds
    that fuse intra-request (`IrInterpreter` vector fan-out).

    Runtime keywords thread straight through — `Session(ctx,
    backend="serve", shards=2, elastic=True, max_inflight=8)` builds a
    sharded runtime exactly like calling `ServeRuntime` directly (the
    `shards=` knob rides the same path `kernel_backend=` does)."""

    name = "serve"

    def __init__(self, ctx, engine=None, *, runtime=None,
                 client_id: str = "session", **runtime_kw):
        from repro.serve.runtime import ServeRuntime
        self._owns_runtime = runtime is None
        self.runtime = runtime if runtime is not None \
            else ServeRuntime(ctx, engine, **runtime_kw)
        # the runtime's Telemetry (passed via runtime_kw or its default):
        # `Session.telemetry` and `metrics()` read through this
        self.telemetry = self.runtime.telemetry
        self.client_id = client_id

    def metrics(self) -> dict:
        return self.runtime.metrics()

    @property
    def scheduler(self):
        return self.runtime.scheduler

    def submit(self, program, enc_inputs: list,
               client_id: Optional[str] = None):
        """Async path: returns the runtime's `RequestHandle`
        (`handle.outputs()` joins)."""
        return self.runtime.submit(program.graph, enc_inputs,
                                   client_id=client_id or self.client_id)

    def execute(self, program, enc_inputs: list) -> list:
        return self.submit(program, enc_inputs).outputs()

    def close(self) -> None:
        if self._owns_runtime:
            self.runtime.close()


_BACKENDS = {"eager": EagerBackend, "local": LocalBackend,
             "serve": ServeBackend}


def make_backend(name: str, ctx, engine=None, *, kernel_backend=None, **kw):
    """Construct a named backend ("eager" | "local" | "serve") over the
    given key material; extra keywords forward to the backend's
    constructor (e.g. `fused=True` for local, `max_inflight=8` or
    `shards=2` for serve).
    `kernel_backend="reference" | "pallas"` selects the engine
    room when no prebuilt engine is passed (see `repro.core.engine`).
    `Session` calls this for string backends; use it directly to share
    one backend across sessions::

        be = make_backend("serve", ctx, engine, max_inflight=4)
        sess = Session(ctx, engine, backend=be)
    """
    try:
        cls = _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r} "
                         f"(have {sorted(_BACKENDS)})") from None
    if kernel_backend is not None:
        if engine is not None:
            raise TypeError("pass kernel_backend OR a prebuilt engine, "
                            "not both")
        from repro.core.engine import TaurusEngine
        engine = TaurusEngine.from_context(ctx, kernel_backend=kernel_backend)
    return cls(ctx, engine, **kw)
