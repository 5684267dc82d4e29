"""GGSW ciphertexts, the bootstrapping key, and the external product.

A GGSW ciphertext of a bit s is a ((k+1)*level, k+1, N) stack of GLWE
rows:  row (u, l) = GLWE_sk(0) + s * g_l * e_u   (Z + s*G).

The external product  GGSW ⊡ GLWE -> GLWE  (paper Fig. 4b) is a
vector-matrix product over polynomials in the transform domain.  Its
Pallas incarnation, `repro.kernels.external_product`, runs inside the
engine's fused PBS path (`TaurusEngine(kernel_backend="pallas")` ->
`repro.kernels.fused_pbs`) against the same plane-layout BSK that
`bsk_to_fourier` builds; this module is the engine room every platform
runs by default and the differential-test oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import fft, glwe, decompose as dec
from repro.core.params import TFHEParams

U64 = jnp.uint64


def encrypt_bit(key: jax.Array, sk: jax.Array, bit: jax.Array,
                base_log: int, level: int, std: float) -> jax.Array:
    """GGSW of a single bit: (k+1, level, k+1, N) uint64."""
    k, N = sk.shape
    rows_msg = jnp.zeros(((k + 1) * level, N), dtype=U64)
    z = glwe.encrypt(key, sk, rows_msg, std)            # ((k+1)*level, k+1, N)
    z = z.reshape(k + 1, level, k + 1, N)
    g = (U64(1) << (U64(64) - U64(base_log) * jnp.arange(1, level + 1, dtype=U64)))
    add = bit.astype(U64)[..., None] * g                # (level,)
    # row (u, l): component u gets + s*g_l at constant coefficient? NO —
    # the gadget adds s*g_l to the WHOLE u-th polynomial's... only the
    # constant monomial when s is a scalar bit: s interpreted as the
    # constant polynomial s.
    upd = z[jnp.arange(k + 1), :, jnp.arange(k + 1), 0] + add[None, :]
    z = z.at[jnp.arange(k + 1), :, jnp.arange(k + 1), 0].set(upd)
    return z


def bsk_gen(key: jax.Array, lwe_sk: jax.Array, glwe_sk: jax.Array,
            params: TFHEParams) -> jax.Array:
    """Bootstrapping key: n GGSW ciphertexts of the small-LWE key bits.

    Returns (n, k+1, level, k+1, N) uint64.
    """
    n = lwe_sk.shape[0]
    keys = jax.random.split(key, n)
    f = lambda kk, bit: encrypt_bit(
        kk, glwe_sk, bit, params.pbs_base_log, params.pbs_level, params.glwe_std
    )
    return jax.vmap(f)(keys, lwe_sk)


def ggsw_to_fourier(ggsw_ct: jax.Array) -> jax.Array:
    """One GGSW (k+1, level, k+1, N) uint64 -> plane layout (2, J, K, M).

    J = (k+1)*level decomposition rows in the order
    `external_product_fourier` contracts over, K = k+1 output polys,
    M = N/2 transform-domain coefficients as float64 (re, im) planes.
    """
    kp1, level, _, N = ggsw_ct.shape
    f = fft.forward(ggsw_ct).reshape(kp1 * level, kp1, 2, N // 2)
    return jnp.moveaxis(f, 2, 0)


def bsk_to_fourier(bsk: jax.Array) -> jax.Array:
    """Pre-transform the BSK once: (n, 2, J, K, M) float64 planes.

    This is the stream the paper's BRU reads from HBM; in the batched
    engine it is the reused operand (key-reuse strategy, §III-B).  The
    Pallas engine room reads the same layout (`kernels.fused_pbs`).
    """
    return jax.vmap(ggsw_to_fourier)(bsk)


def external_product_fourier(ggsw_f: jax.Array, glwe_ct: jax.Array,
                             base_log: int, level: int) -> jax.Array:
    """GGSW (planes, (2, J, K, M)) ⊡ GLWE ((k+1, N)) -> GLWE.

    Batched over leading axes of `glwe_ct`.
    """
    *lead, kp1, N = glwe_ct.shape
    digits = dec.decompose(glwe_ct, base_log, level)     # (..., k+1, N, level)
    digits = jnp.moveaxis(digits, -1, -2)                # (..., k+1, level, N)
    dig_f = fft.forward(digits.reshape(*lead, kp1 * level, N))
    return fft.inverse_torus(fft.mac(dig_f, ggsw_f))     # (..., k+1, N)


def cmux_fourier(ggsw_f: jax.Array, ct0: jax.Array, ct1: jax.Array,
                 base_log: int, level: int) -> jax.Array:
    """CMux: returns ct0 if the GGSW bit is 0 else ct1."""
    return ct0 + external_product_fourier(ggsw_f, ct1 - ct0, base_log, level)
