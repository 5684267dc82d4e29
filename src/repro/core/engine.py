"""TaurusEngine: the paper's 4-cluster accelerator as a mesh of devices.

Mapping (paper -> here):
  compute cluster            -> one mesh device on the `data` axis
  12 round-robin cts/cluster -> `batch_per_device` (default 12)
  48-ct scheduling batch     -> engine.batch_size = 12 * n_devices
  global BSK/KSK buffer +NoC -> keys replicated across the mesh
  full synchronization       -> one SPMD program per PBS batch (Obs. 5)

The engine is the execution backend for `repro.compiler` schedules and
the unit benchmarks in `benchmarks/`.

Kernel backends: `kernel_backend="reference"` (default) runs the jax
PBS in `repro.core.batch`, whose CMux steps follow the BSK layout
`ggsw.bsk_to_fourier` built for the platform (exact int8 matmuls on a
TPU, float64 re/im planes elsewhere); `"pallas"` runs the fused Pallas
engine room (`repro.kernels.fused_pbs`) — same KS-first pipeline, but the FFT /
external-product / keyswitch stages execute as Pallas kernels against a
`FusedPbsPack` of resident transform-domain key operands (built lazily
on first `lut_batch`, reused across every round — the paper's key-reuse
strategy).  Both backends are decrypt-identical; the keyswitch stage is
bit-identical.  The pallas room runs in interpret mode on CPU only and
is refused on a TPU (see `ConfigError`).

Timing: each engine-room execution is handed to
`repro.obs.watch_execution` as it is enqueued.  Under a span of a
tracing `Telemetry` (the scheduler's `fused_round`, a request's span)
the telemetry's watcher records it as an `engine_room` span: its busy
interval on the device, the program's name as the device trace prints
it, its real and dispatched rows and the layout of its bootstrapping
key (`ggsw.key_layout`).  Otherwise that costs one thread-local lookup.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import batch as batch_mod, ggsw, glwe, lwe, torus
from repro.core.params import TFHEParams
from repro.obs.trace import watch_execution

U64 = jnp.uint64


class ConfigError(ValueError):
    """An unsupported engine/runtime configuration, rejected at
    construction time (not at first `lut_batch`).

    Supported (kernel_backend, mesh) combinations:

      reference + mesh=None   single-device jax reference PBS
      reference + mesh        SPMD `pbs_batch` sharded over the data axis
      pallas    + mesh=None   fused Pallas engine room, per-device

    pallas + mesh is NOT supported: the fused kernels run per-device.
    The sharded `ServeRuntime` routes around this — a multi-device shard
    requesting the pallas backend gets a single-device engine instead of
    raising here (see `repro.serve.shard.build_shards`).

    pallas on a TPU is NOT supported either: Mosaic has no float64, and
    float32 transform planes lack the precision of the 64-bit torus;
    the reference backend's int8 room is the TPU's."""


def validate_lut_tables(cts: jax.Array, tables, params: TFHEParams):
    """Normalize/validate per-ciphertext integer LUT tables against a
    batch: broadcast a single (2^width,) table across the batch, reject
    any other count mismatch (it used to slip through as a silent shape
    mismatch inside the jitted PBS).  Shared by `TaurusEngine` and the
    serving `FusedEngineProxy` so their validation cannot drift."""
    tables = jnp.asarray(tables, dtype=U64)
    mod = params.plaintext_modulus
    if tables.ndim == 1:
        tables = jnp.broadcast_to(tables, (cts.shape[0],) + tables.shape)
    if tables.ndim != 2 or tables.shape[-1] != mod:
        raise ValueError(
            f"lut_batch_tables: tables must be (B, {mod}) or ({mod},), "
            f"got {tuple(tables.shape)}")
    if tables.shape[0] != cts.shape[0]:
        raise ValueError(
            f"lut_batch_tables: {cts.shape[0]} ciphertexts but "
            f"{tables.shape[0]} tables — pass one table per ciphertext "
            f"or a single shared table")
    return tables


@dataclasses.dataclass
class TaurusEngine:
    params: TFHEParams
    bsk_f: jax.Array
    ksk: jax.Array
    mesh: Optional[Mesh] = None
    data_axis: str = "data"
    batch_per_device: int = 12  # paper's round-robin depth (Fig. 13b)
    # "reference" = jax PBS in repro.core.batch; "pallas" = fused kernel
    # path in repro.kernels.fused_pbs (interpret mode, CPU only).
    kernel_backend: str = "reference"
    # single-device engines: the device that holds the keys and runs
    # every round (None = wherever the keys already are).  A sharded
    # ServeRuntime pins each shard's engine to its own device.
    device: Optional[jax.Device] = None

    def __post_init__(self):
        if self.kernel_backend not in ("reference", "pallas"):
            raise ValueError(
                f"kernel_backend must be 'reference' or 'pallas', "
                f"got {self.kernel_backend!r}")
        if self.kernel_backend == "pallas" and jax.default_backend() == "tpu":
            raise ConfigError(
                "kernel_backend='pallas' is not supported on TPU: Mosaic "
                "has no float64, and float32 transform planes lack the "
                "precision of the 64-bit torus. Use the reference "
                "backend, which runs exact int8 matmuls on the MXU.")
        if self.mesh is not None and self.device is not None:
            raise ConfigError("pass mesh OR device, not both")
        if self.kernel_backend == "pallas" and self.mesh is not None:
            raise ConfigError(
                "kernel_backend='pallas' + mesh is not a supported engine "
                "configuration — the fused kernels run per-device. "
                "Supported combinations: reference + mesh=None, "
                "reference + mesh, pallas + mesh=None. Use the reference "
                "backend for multi-cluster meshes, or drop the mesh for "
                "the pallas engine room (the sharded ServeRuntime does "
                "the latter automatically).")
        if self.mesh is not None:
            # replicate the keys once, not on every sharded round
            repl = NamedSharding(self.mesh, P())
            self.bsk_f, self.ksk = jax.device_put((self.bsk_f, self.ksk), repl)
        elif self.device is not None:
            self.bsk_f, self.ksk = self.place(self.bsk_f, self.ksk)

    def place(self, *arrays):
        """Move round inputs onto the engine's pinned device (no-op for
        an unpinned engine)."""
        if self.device is None:
            return arrays
        return jax.device_put(arrays, SingleDeviceSharding(self.device))

    # -- derived -----------------------------------------------------------
    @property
    def key_bytes(self) -> tuple:
        """(bsk_bytes, ksk_bytes) of the evaluation keys as streamed per
        PBS round — the quantity the bandwidth ledger accounts."""
        kb = getattr(self, "_key_bytes", None)
        if kb is None:
            kb = self._key_bytes = (
                int(self.bsk_f.size) * self.bsk_f.dtype.itemsize,
                int(self.ksk.size) * self.ksk.dtype.itemsize)
        return kb

    @property
    def fused_pack(self):
        """The resident `FusedPbsPack` for the pallas backend, built on
        first use and cached — every later `lut_batch` round reuses the
        same transform-domain key arrays (the paper's key reuse)."""
        pack = getattr(self, "_fused_pack", None)
        if pack is None:
            from repro.kernels.fused_pbs import FusedPbsPack
            pack = self._fused_pack = FusedPbsPack.build(
                self.bsk_f, self.ksk, self.params)
        return pack

    @property
    def _mesh_pbs(self):
        """`pbs_batch` jitted once for the mesh: rows sharded over the
        data axis, keys replicated."""
        fn = getattr(self, "_mesh_pbs_fn", None)
        if fn is None:
            data_sh = NamedSharding(self.mesh, P(self.data_axis))
            repl = NamedSharding(self.mesh, P())
            fn = self._mesh_pbs_fn = jax.jit(
                batch_mod.pbs_batch.__wrapped__,
                static_argnames=("params",),
                in_shardings=(data_sh, data_sh, repl, repl),
                out_shardings=data_sh,
            )
        return fn

    @property
    def n_clusters(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.data_axis]

    @property
    def supports_ks_split(self) -> bool:
        """Whether `keyswitch` + `lut_batch_small` may replace a
        `lut_batch` (the serving scheduler's KS-level partial dedup).
        Single-device engines only: the mesh path runs one SPMD program
        per full PBS round and has no sharded half-round entry."""
        return self.mesh is None

    @property
    def batch_size(self) -> int:
        return self.batch_per_device * self.n_clusters

    # -- linear ops (LPU; no bootstrapping, Fig. 2b step 4) -----------------
    def add(self, a, b):
        return lwe.add(a, b)

    def sub(self, a, b):
        return lwe.sub(a, b)

    def scalar_mul(self, a, c):
        return lwe.scalar_mul(a, c)

    def add_plain(self, a, msg):
        return lwe.add_plain(a, torus.encode(jnp.asarray(msg, dtype=U64), self.params.delta))

    def trivial(self, msg) -> jax.Array:
        m = torus.encode(jnp.asarray(msg, dtype=U64), self.params.delta)
        return lwe.trivial(m, self.params.big_n)

    # -- PBS (BRU + LPU pipeline) -------------------------------------------
    def lut_batch(self, cts: jax.Array, lut_polys: jax.Array,
                  rows: Optional[int] = None) -> jax.Array:
        """Apply per-ciphertext LUTs with noise refresh.

        cts: (B, k*N+1); lut_polys: (B, N) torus polys
        (`glwe.make_lut_poly` encodes integer tables).
        Pads B up to a multiple of the cluster count.  `rows`: how many
        of the B rows are real (the caller padded the rest to a compiled
        width); it only labels the execution's `engine_room` span.
        """
        B = cts.shape[0]
        rows = B if rows is None else rows
        if lut_polys.shape[0] != B:
            raise ValueError(
                f"lut_batch: {B} ciphertexts but {lut_polys.shape[0]} LUT "
                f"polynomials — counts must match per batch row")
        shards = self.n_clusters
        pad = (-B) % shards
        if pad:
            cts = jnp.concatenate([cts, cts[:pad]], axis=0)
            lut_polys = jnp.concatenate([lut_polys, lut_polys[:pad]], axis=0)
        cts, lut_polys = self.place(cts, lut_polys)
        if self.mesh is None:
            if self.kernel_backend == "pallas":
                out = self.fused_pack.pbs_batch(cts, lut_polys)
                program = "pbs_batch_fused"
            else:
                out = batch_mod.pbs_batch(cts, lut_polys, self.bsk_f,
                                          self.ksk, self.params)
                program = "pbs_batch"
        else:
            # rows may arrive committed to another layout (a previous
            # round's sharded output): reshard them onto the data axis
            data_sh = NamedSharding(self.mesh, P(self.data_axis))
            cts, lut_polys = jax.device_put((cts, lut_polys), data_sh)
            out = self._mesh_pbs(cts, lut_polys, self.bsk_f, self.ksk,
                                 self.params)
            program = "pbs_batch"
        watch_execution(out, program=program, rows=rows, padded=B + pad,
                        layout=ggsw.key_layout(self.bsk_f))
        if self.mesh is not None:
            # hand the round back on one device: request threads
            # then run single-device ops only, never concurrent
            # multi-device programs whose collectives could pair up
            # in different orders on different devices and deadlock
            out = jax.device_put(
                out, SingleDeviceSharding(self.mesh.devices.flat[0]))
        return out[:B]

    # -- the split PBS entries (KS-level partial dedup, ISSUE 10) -----------
    def keyswitch(self, big_cts: jax.Array,
                  rows: Optional[int] = None) -> jax.Array:
        """The keyswitch stage alone: (B, k*N+1) big-key cts ->
        (B, n+1) small-key cts.  Bit-identical to the first stage of
        `lut_batch` on both backends (the pallas limb kernel is exact
        mod 2^64), so key-switching each UNIQUE ciphertext once and
        fanning the result out across its tables is decrypt-identical
        to key-switching every row."""
        if not self.supports_ks_split:
            raise ConfigError(
                "keyswitch/lut_batch_small need a single-device engine "
                "(supports_ks_split) — the mesh path dispatches full PBS "
                "rounds only")
        B = big_cts.shape[0]
        rows = B if rows is None else rows
        (big_cts,) = self.place(big_cts)
        if self.kernel_backend == "pallas":
            out = self.fused_pack.keyswitch(big_cts)
            program = "keyswitch_fused"
        else:
            out = batch_mod.keyswitch_batch_jit(big_cts, self.ksk,
                                                self.params)
            program = "keyswitch_batch_jit"
        watch_execution(out, program=program, rows=rows, padded=B,
                        layout=ggsw.key_layout(self.bsk_f))
        return out

    def lut_batch_small(self, small_cts: jax.Array, lut_polys: jax.Array,
                        rows: Optional[int] = None) -> jax.Array:
        """`lut_batch` minus the keyswitch: (B, n+1) small-key cts +
        (B, N) LUT polys -> (B, k*N+1) refreshed big-key cts.
        `keyswitch` then `lut_batch_small` computes exactly what
        `lut_batch` computes."""
        if not self.supports_ks_split:
            raise ConfigError(
                "lut_batch_small needs a single-device engine "
                "(supports_ks_split) — the mesh path dispatches full PBS "
                "rounds only")
        B = small_cts.shape[0]
        rows = B if rows is None else rows
        if lut_polys.shape[0] != B:
            raise ValueError(
                f"lut_batch_small: {B} ciphertexts but "
                f"{lut_polys.shape[0]} LUT polynomials — counts must "
                f"match per batch row")
        small_cts, lut_polys = self.place(small_cts, lut_polys)
        if self.kernel_backend == "pallas":
            out = self.fused_pack.pbs_from_small(small_cts, lut_polys)
            program = "pbs_small_fused"
        else:
            out = batch_mod.pbs_batch_small(small_cts, lut_polys,
                                            self.bsk_f, self.params)
            program = "pbs_batch_small"
        watch_execution(out, program=program, rows=rows, padded=B,
                        layout=ggsw.key_layout(self.bsk_f))
        return out

    def lut_batch_tables(self, cts: jax.Array, tables) -> jax.Array:
        """lut_batch from per-ciphertext INTEGER tables (B, 2^width):
        encodes each row as a test polynomial, then one batched PBS.

        A single 1-D table (2^width,) broadcasts across the whole batch;
        any other count mismatch raises (see `validate_lut_tables`)."""
        tables = validate_lut_tables(cts, tables, self.params)
        return self.lut_batch(cts,
                              glwe.make_lut_polys_cached(tables, self.params))

    def lut_batch_xpu(self, cts: jax.Array, lut_polys: jax.Array) -> jax.Array:
        """Morphling-XPU-style baseline: no cross-ciphertext BSK reuse."""
        return batch_mod.pbs_unbatched_loop(
            cts, lut_polys, self.bsk_f, self.ksk, self.params
        )

    @classmethod
    def from_context(cls, ctx, mesh: Optional[Mesh] = None, **kw) -> "TaurusEngine":
        return cls(ctx.params, ctx.bsk_f, ctx.ksk, mesh=mesh, **kw)
