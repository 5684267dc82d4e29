"""Batched PBS — the paper's round-robin BSK reuse (§III-B), TPU-native.

Taurus's BRU round-robins 12 ciphertexts through one wide FFT pipeline so
each BSK chunk streamed from HBM is consumed by ALL in-flight ciphertexts.
On TPU the same insight is a BATCH dimension: one blind-rotation iteration
loads bsk_f[i] once and applies it to the whole ciphertext batch in one
external product (on a TPU, one int8 matmul whose rows are the batch's
digit blocks).  Arithmetic intensity on the
BSK stream scales linearly with the batch size, exactly the paper's Fig. 13
bandwidth argument.

All functions here are the BATCHED versions of `repro.core.pbs`; the
unbatched path (used as the Morphling-XPU comparison baseline in
benchmarks) simply sets B=1 per call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import ggsw, glwe, lwe
from repro.core.params import TFHEParams

U64 = jnp.uint64


def rotate_batch(cts: jax.Array, rs: jax.Array, N: int) -> jax.Array:
    """Monomial-rotate a batch: cts (B, k+1, N), rs (B,) in [0, 2N)."""
    return jax.vmap(lambda c, r: glwe.rotate(c, r, N))(cts, rs)


def blind_rotate_batch(lut_glwes: jax.Array, ms_cts: jax.Array,
                       bsk_f: jax.Array, params: TFHEParams) -> jax.Array:
    """Batched blind rotation.

    lut_glwes: (B, k+1, N); ms_cts: (B, n+1) mod-switched to [0, 2N);
    bsk_f: the BSK in `ggsw.bsk_to_fourier`'s layout — scanned once,
    shared across batch.
    """
    N = params.N
    a, b = ms_cts[:, :-1], ms_cts[:, -1]
    acc = rotate_batch(lut_glwes, (2 * N - b) % (2 * N), N)

    def step(acc, inp):
        a_i, bsk_i = inp                                # a_i: (B,)
        with jax.named_scope("rotate"):
            diff = rotate_batch(acc, a_i, N) - acc
        # one GGSW applied to the whole batch: the key-reuse MAC
        acc = acc + ggsw.external_product_fourier(
            bsk_i, diff, params.pbs_base_log, params.pbs_level
        )
        return acc, None

    acc, _ = jax.lax.scan(step, acc, (a.T, bsk_f))
    return acc


def keyswitch_batch(big_cts: jax.Array, ksk: jax.Array,
                    params: TFHEParams) -> jax.Array:
    """(B, k*N+1) -> (B, n+1); one wraparound multiply-reduce (LPU)."""
    return lwe.keyswitch(big_cts, ksk, params.ks_base_log, params.ks_level)


@functools.partial(jax.jit, static_argnames=("params",))
def pbs_batch(big_cts: jax.Array, lut_polys: jax.Array, bsk_f: jax.Array,
              ksk: jax.Array, params: TFHEParams) -> jax.Array:
    """Batch of full PBS ops: (B, k*N+1) + (B, N) LUTs -> (B, k*N+1)."""
    small = keyswitch_batch(big_cts, ksk, params)
    ms = lwe.mod_switch(small, params.log2_N + 1)
    luts = glwe.trivial(lut_polys, params.k)
    acc = blind_rotate_batch(luts, ms, bsk_f, params)
    return glwe.sample_extract(acc)


@functools.partial(jax.jit, static_argnames=("params",))
def keyswitch_batch_jit(big_cts: jax.Array, ksk: jax.Array,
                        params: TFHEParams) -> jax.Array:
    """Standalone jitted keyswitch stage — the first half of `pbs_batch`,
    split out so the serving scheduler can key-switch a batch of UNIQUE
    ciphertexts once and fan the small-key results out to every
    (ciphertext, table) row that shares them (KS-level partial dedup)."""
    return keyswitch_batch(big_cts, ksk, params)


@functools.partial(jax.jit, static_argnames=("params",))
def pbs_batch_small(small_cts: jax.Array, lut_polys: jax.Array,
                    bsk_f: jax.Array, params: TFHEParams) -> jax.Array:
    """PBS minus the keyswitch: (B, n+1) small-key cts + (B, N) LUTs ->
    (B, k*N+1).  Composing `keyswitch_batch_jit` then this function is
    arithmetically identical to `pbs_batch` — both run the same
    mod-switch / blind-rotate / sample-extract stages on the same
    small-key ciphertexts."""
    ms = lwe.mod_switch(small_cts, params.log2_N + 1)
    luts = glwe.trivial(lut_polys, params.k)
    acc = blind_rotate_batch(luts, ms, bsk_f, params)
    return glwe.sample_extract(acc)


@functools.partial(jax.jit, static_argnames=("params",))
def pbs_unbatched_loop(big_cts: jax.Array, lut_polys: jax.Array,
                       bsk_f: jax.Array, ksk: jax.Array,
                       params: TFHEParams) -> jax.Array:
    """XPU-style baseline: process ciphertexts one at a time (no BSK
    reuse across ciphertexts).  Same math, B× the BSK traffic — used by
    the Table IV comparison benchmark."""
    from repro.core import pbs as pbs_mod

    def one(ct, lut):
        small = lwe.keyswitch(ct, ksk, params.ks_base_log, params.ks_level)
        ms = lwe.mod_switch(small, params.log2_N + 1)
        acc = pbs_mod.blind_rotate(glwe.trivial(lut, params.k), ms, bsk_f, params)
        return glwe.sample_extract(acc)

    return jax.lax.map(lambda args: one(*args), (big_cts, lut_polys))
