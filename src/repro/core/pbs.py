"""Programmable bootstrapping, key-switching-FIRST order (paper §II-B).

Pipeline (paper Fig. 3):  A key-switch -> B mod-switch -> C blind rotation
-> D sample extract.  Ciphertexts between PBS ops live under the BIG key
(dimension k*N); key-switch brings them down to the small key (dimension
n) right before blind rotation.  This order is what enables the
compiler's KS-dedup (Observation 6).

`TFHEContext` bundles keygen + client ops; `pbs()` is the server op.
The batched variant lives in `repro.core.batch` (reference) and
`repro.kernels.fused_pbs` (Pallas engine room) — `TaurusEngine`
selects between them via `kernel_backend`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import torus, glwe, ggsw, lwe
from repro.core.params import TFHEParams

U64 = jnp.uint64


def blind_rotate(lut_glwe: jax.Array, lwe_ct_mod: jax.Array,
                 bsk_f: jax.Array, params: TFHEParams) -> jax.Array:
    """Blind rotation (paper step C).

    lut_glwe: (k+1, N) trivial/encrypted GLWE holding the LUT.
    lwe_ct_mod: (n+1,) uint64 values already mod-switched into [0, 2N).
    bsk_f: (n, 2, J, K, N/2) plane-layout fourier BSK.
    """
    N = params.N
    a, b = lwe_ct_mod[:-1], lwe_ct_mod[-1]
    acc = glwe.rotate(lut_glwe, (2 * N - b) % (2 * N), N)   # X^{-b} * V

    def step(acc, inp):
        a_i, bsk_i = inp
        rotated = glwe.rotate(acc, a_i, N)                  # X^{a_i} * acc
        return ggsw.cmux_fourier(
            bsk_i, acc, rotated, params.pbs_base_log, params.pbs_level
        ), None

    acc, _ = jax.lax.scan(step, acc, (a, bsk_f))
    return acc


@functools.partial(jax.jit, static_argnames=("params",))
def pbs(big_ct: jax.Array, lut_poly: jax.Array, bsk_f: jax.Array,
        ksk: jax.Array, params: TFHEParams) -> jax.Array:
    """One full PBS: (k*N+1,) LWE + (N,) LUT poly -> (k*N+1,) LWE.

    Output has the LUT applied and noise refreshed.
    """
    # A: key-switch big -> small
    small = lwe.keyswitch(big_ct, ksk, params.ks_base_log, params.ks_level)
    # B: mod-switch to Z_2N
    ms = lwe.mod_switch(small, params.log2_N + 1)
    # C: blind rotation
    acc = blind_rotate(glwe.trivial(lut_poly, params.k), ms, bsk_f, params)
    # D: sample extract back to the big key
    return glwe.sample_extract(acc)


@functools.partial(jax.jit, static_argnames=("params",))
def _keygen(key: jax.Array, params: TFHEParams):
    """Secret keys and evaluation keys, as one program on the default
    device: (lwe_sk, glwe_sk, big_sk, bsk_f, ksk)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    lwe_sk = lwe.keygen(k1, params.n)
    glwe_sk = glwe.keygen(k2, params.k, params.N)
    big_sk = glwe.flatten_key(glwe_sk)
    bsk = ggsw.bsk_gen(k3, lwe_sk, glwe_sk, params)
    bsk_f = ggsw.bsk_to_fourier(bsk)
    ksk = lwe.ksk_gen(k4, big_sk, lwe_sk,
                      params.ks_base_log, params.ks_level, params.lwe_std)
    return lwe_sk, glwe_sk, big_sk, bsk_f, ksk


@dataclasses.dataclass
class TFHEContext:
    """Client-side key material + encode/encrypt helpers (Fig. 1 client)."""
    params: TFHEParams
    lwe_sk: jax.Array      # small key (n,)
    glwe_sk: jax.Array     # (k, N)
    big_sk: jax.Array      # flattened GLWE key (k*N,)
    bsk_f: jax.Array       # fourier bootstrapping key, f64 planes (eval key)
    ksk: jax.Array         # key-switching key big->small (server/eval key)

    @classmethod
    def create(cls, key: jax.Array, params: TFHEParams) -> "TFHEContext":
        return cls(params, *_keygen(key, params))

    # -- client ops ------------------------------------------------------
    def encrypt(self, key: jax.Array, msg) -> jax.Array:
        """Encrypt integer message(s) under the BIG key (PBS-ready)."""
        m = torus.encode(jnp.asarray(msg, dtype=U64), self.params.delta)
        return lwe.encrypt(key, self.big_sk, m, self.params.glwe_std)

    def decrypt(self, ct: jax.Array) -> jax.Array:
        ph = lwe.decrypt_phase(self.big_sk, ct)
        return torus.decode(ph, self.params.delta, self.params.plaintext_modulus)

    def decrypt_noise(self, ct: jax.Array, msg) -> jax.Array:
        """Signed residual noise (torus units) for noise-budget tests."""
        ph = lwe.decrypt_phase(self.big_sk, ct)
        expect = torus.encode(jnp.asarray(msg, dtype=U64), self.params.delta)
        return torus.to_signed(ph - expect).astype(jnp.float64) / 2.0**64

    # -- server op ---------------------------------------------------------
    def lut(self, ct: jax.Array, table) -> jax.Array:
        poly = glwe.make_lut_poly(jnp.asarray(table, dtype=U64), self.params)
        return pbs(ct, poly, self.bsk_f, self.ksk, self.params)
