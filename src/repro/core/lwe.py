"""LWE ciphertexts and the LPU-side operations (paper §IV-A).

Ciphertext layout: (..., n+1) uint64 = [a_0 .. a_{n-1}, b].
All functions are batched over leading axes.

Key-switching here is the paper's most expensive LPU op.  The Pallas
uint32-limb version in `repro.kernels.keyswitch`
(`repro.kernels.fused_pbs.keyswitch_fused`, the
`TaurusEngine(kernel_backend="pallas")` path) is BIT-IDENTICAL to
`keyswitch` below — both are exact mod 2^64, pinned by
`tests/test_kernels.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import torus, decompose as dec

U64 = jnp.uint64


# --- keys & encryption (client side; the server never holds these) ----------

def keygen(key: jax.Array, n: int) -> jax.Array:
    """Binary LWE secret key, shape (n,) uint64 in {0,1}."""
    return jax.random.bernoulli(key, 0.5, (n,)).astype(U64)


def encrypt(key: jax.Array, sk: jax.Array, msg_torus: jax.Array, std: float) -> jax.Array:
    """Encrypt torus element(s).  msg_torus: (...,) uint64 -> (..., n+1)."""
    n = sk.shape[0]
    shape = msg_torus.shape
    ka, ke = jax.random.split(key)
    a = torus.random_torus(ka, shape + (n,))
    e = torus.gaussian_noise(ke, shape, std)
    b = (a * sk).sum(axis=-1, dtype=U64) + msg_torus + e
    return jnp.concatenate([a, b[..., None]], axis=-1)


def decrypt_phase(sk: jax.Array, ct: jax.Array) -> jax.Array:
    """Return the noisy phase b - <a, s>  (caller rounds/decodes)."""
    a, b = ct[..., :-1], ct[..., -1]
    return b - (a * sk).sum(axis=-1, dtype=U64)


def trivial(msg_torus: jax.Array, n: int) -> jax.Array:
    """Noiseless 'trivial' ciphertext (a=0, b=m) — public constant."""
    z = jnp.zeros(msg_torus.shape + (n,), dtype=U64)
    return jnp.concatenate([z, msg_torus[..., None].astype(U64)], axis=-1)


# --- linear homomorphic ops (LPU VecAdd / VecMult) ---------------------------

def add(ct0: jax.Array, ct1: jax.Array) -> jax.Array:
    return ct0 + ct1  # uint64 wraparound == torus addition


def sub(ct0: jax.Array, ct1: jax.Array) -> jax.Array:
    return ct0 - ct1


def scalar_mul(ct: jax.Array, c) -> jax.Array:
    """Multiply by a plaintext (small) integer."""
    return ct * jnp.asarray(c, dtype=jnp.int64).astype(U64)


def add_plain(ct: jax.Array, msg_torus) -> jax.Array:
    return ct.at[..., -1].add(jnp.asarray(msg_torus, dtype=U64))


# --- modulus switching (paper step B) ----------------------------------------

def mod_switch(ct: jax.Array, log2_2N: int) -> jax.Array:
    """Scale torus values from q=2^64 to Z_{2N}; returns uint64 in [0, 2N)."""
    shift = 64 - log2_2N
    rounded = (ct >> U64(shift - 1)) + U64(1)
    return (rounded >> U64(1)) & U64((1 << log2_2N) - 1)


# --- key switching (paper step A; KS-first order) -----------------------------

def ksk_gen(key: jax.Array, sk_from: jax.Array, sk_to: jax.Array,
            base_log: int, level: int, std: float) -> jax.Array:
    """Key-switching key: (n_from, level, n_to+1) uint64.

    KSK[i, l] = LWE_{sk_to}( sk_from[i] * g_l ),  g_l = 2^(64-(l+1)*base_log)
    """
    n_from = sk_from.shape[0]
    g = (U64(1) << (U64(64) - U64(base_log) * jnp.arange(1, level + 1, dtype=U64)))
    msgs = sk_from[:, None] * g[None, :]           # (n_from, level)
    return encrypt(key, sk_to, msgs, std)


def keyswitch(ct: jax.Array, ksk: jax.Array, base_log: int, level: int) -> jax.Array:
    """Switch (..., n_from+1) under sk_from to (..., n_to+1) under sk_to."""
    n_from = ksk.shape[0]
    n_to = ksk.shape[-1] - 1
    a, b = ct[..., :-1], ct[..., -1]
    digits = dec.decompose(a, base_log, level)      # (..., n_from, level) int64
    # out = (0, b) - sum_{i,l} digit * KSK[i,l], as a wraparound multiply
    # and reduce (the digit cast is two's-complement-correct).  Not a
    # dot: XLA:TPU has no uint64 dot, but fuses this into one reduction.
    acc = jnp.sum(digits.astype(U64)[..., None] * ksk, axis=(-3, -2),
                  dtype=U64)
    out = -acc
    out = out.at[..., -1].add(b)
    return out
