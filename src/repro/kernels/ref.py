"""Pure-jnp oracles for every Pallas kernel (the grading contract).

These run in f64 / uint64 (CPU gold path) and define bit-level or
tolerance-level expectations for the kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import fft as core_fft

U64 = jnp.uint64


def fft_forward_ref(x: jax.Array) -> jax.Array:
    """real (B, N) -> (B, 2, N/2) f64 stacked re/im (same layout as kernel)."""
    return core_fft.forward(x.astype(jnp.float64))


def fft_inverse_ref(spec: jax.Array) -> jax.Array:
    """(B, 2, M) -> real (B, 2M) f64."""
    return core_fft.inverse(spec.astype(jnp.float64))


def external_product_mac_ref(dig: jax.Array, bsk: jax.Array) -> jax.Array:
    """dig (B,2,J,F), bsk (2,J,K,F) -> (B,2,K,F), f64 plane math."""
    out = core_fft.mac(jnp.swapaxes(dig, 1, 2).astype(jnp.float64),
                       bsk.astype(jnp.float64))            # (B, K, 2, F)
    return jnp.swapaxes(out, 1, 2)


def keyswitch_mac_ref(digits: jax.Array, ksk: jax.Array) -> jax.Array:
    """digits (B, S) int32, ksk (S, T) uint64 -> (B, T) uint64 mod 2^64.

    Exact uint64 oracle for the limb kernel.
    """
    d = digits.astype(jnp.int64).astype(U64)     # two's complement mod 2^64
    return jnp.sum(d[:, :, None] * ksk, axis=1, dtype=U64)     # wraps


def split_u64(x: jax.Array):
    """uint64 -> (hi, lo) uint32 planes (kernel input format)."""
    return (x >> U64(32)).astype(jnp.uint32), (x & U64(0xFFFFFFFF)).astype(jnp.uint32)


def merge_u64(hi: jax.Array, lo: jax.Array) -> jax.Array:
    return (hi.astype(U64) << U64(32)) | lo.astype(U64)
