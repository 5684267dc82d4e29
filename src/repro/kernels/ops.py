"""Jit'd public wrappers over the Pallas kernels.

Interpret mode follows the platform (`repro.kernels.interpret_mode`):
on for CPU, off on a TPU, with identical call signatures.

`dtype` selects the transform-plane precision: f32 is the TPU-native
mode, f64 is what the fused engine path (`repro.kernels.fused_pbs`)
runs so the 64-bit torus noise budget holds in interpret mode.  The
keyswitch MAC is uint32-limb exact regardless.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import fourstep_fft, external_product, keyswitch, ref


def negacyclic_fft(x: jax.Array, *, dtype=jnp.float32) -> jax.Array:
    """Forward negacyclic transform, (B, N) real -> (B, 2, N/2) planes."""
    return fourstep_fft.fft_forward(x, dtype=dtype)


def negacyclic_ifft(spec: jax.Array, *, dtype=jnp.float32) -> jax.Array:
    """(B, 2, M) -> (B, 2M) plane-dtype coefficients."""
    return fourstep_fft.fft_inverse(spec, dtype=dtype)


def bru_mac(dig: jax.Array, bsk: jax.Array, *, block_f: int = 2048,
            dtype=jnp.float32) -> jax.Array:
    """Blind-rotation MAC: (B,2,J,F) x (2,J,K,F) -> (B,2,K,F)."""
    return external_product.external_product_mac(
        dig, bsk, block_f=block_f, dtype=dtype
    )


def lpu_keyswitch_mac(digits: jax.Array, ksk_u64: jax.Array,
                      *, block_s: int = 1024) -> jax.Array:
    """digits (B,S) int32 x ksk (S,T) uint64 -> (B,T) uint64 (mod 2^64)."""
    hi, lo = ref.split_u64(ksk_u64)
    ohi, olo = keyswitch.keyswitch_mac(
        digits, hi, lo, block_s=block_s
    )
    return ref.merge_u64(ohi, olo)
