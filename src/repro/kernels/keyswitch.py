"""LPU key-switch MAC kernel: 64-bit torus arithmetic from uint32 limbs.

TPU has no uint64 — the paper's LPU is a 64-bit integer vector unit, so
the TPU adaptation synthesizes mod-2^64 arithmetic from uint32 limb pairs
(hi, lo) with explicit carries.  16-bit sub-limb partial products keep
every intermediate inside uint32.

Computes   acc[b, t] = sum_{s} d[b, s] * K[s, t]   (mod 2^64)

where s flattens (n_from, level), d are signed gadget digits (int32,
interpreted mod 2^64 as two's complement), and K is the key-switching key
as (hi, lo) uint32 planes.  The caller forms  out = (0..0, b) - acc.

Accumulation strategy (fully vectorized, no sequential carries): partial
products are accumulated per 16-bit lane into uint32 accumulators, then
lanes are recombined with carry propagation once per block.  A block of
S_BLK <= 4096 terms keeps every lane accumulator < 2^32.  Blocks combine
across grid steps mod 2^64 (sequential grid accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import numpy as np

from repro.kernels import interpret_mode

U32 = np.uint32
ROWS = 8  # ciphertexts per grid step: the sublane tile of the batch axis
MASK16 = np.uint32(0xFFFF)


def _mul64(du_hi, du_lo, k_hi, k_lo):
    """(du_hi,du_lo) * (k_hi,k_lo) mod 2^64, all uint32, via 16-bit parts.

    Broadcasting: du_* are (..., 1), k_* are (S, T)-shaped blocks.
    Returns (hi, lo) uint32.
    """
    a0 = du_lo & MASK16
    a1 = du_lo >> U32(16)
    b0 = k_lo & MASK16
    b1 = k_lo >> U32(16)
    # full 64-bit product of the two low words
    p00 = a0 * b0                       # < 2^32
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = p01 + p10                     # may wrap: detect carry
    mid_c = (mid < p01).astype(U32)     # carry into bit 32 of (mid << 16)
    lo = p00 + (mid << U32(16))
    lo_c = (lo < p00).astype(U32)
    hi = p11 + (mid >> U32(16)) + (mid_c << U32(16)) + lo_c
    # cross terms only affect the high word (mod 2^64)
    hi = hi + du_lo * k_hi + du_hi * k_lo
    return hi, lo


def _row_mac(d, k_hi, k_lo):
    """One ciphertext's block MAC: d (S, 1) int32 digits against the
    (S, T) key block -> (hi, lo) (T,) uint32 block sum mod 2^64."""
    du_lo = d.astype(U32)                        # two's complement low word
    du_hi = (d >> 31).astype(U32)                # sign-extension high word
    p_hi, p_lo = _mul64(du_hi, du_lo, k_hi, k_lo)
    # lane-wise accumulation: sum 16-bit lanes of p_lo/p_hi.  Each lane
    # sum < S_BLK * 2^16 <= 2^28 for S_BLK <= 4096, so it is summed as
    # int32 (Mosaic reduces no unsigned type) and read back as uint32.
    lane_sum = lambda x: jnp.sum(x.astype(jnp.int32), axis=0,
                                 dtype=jnp.int32).astype(U32)
    s_lo0 = lane_sum(p_lo & MASK16)
    s_lo1 = lane_sum(p_lo >> U32(16))
    s_hi0 = lane_sum(p_hi & MASK16)
    s_hi1 = lane_sum(p_hi >> U32(16))
    # recombine with carries
    blk_lo = s_lo0 + (s_lo1 << U32(16))
    carry = (s_lo1 + (s_lo0 >> U32(16))) >> U32(16)
    blk_hi = s_hi0 + (s_hi1 << U32(16)) + carry
    return blk_hi, blk_lo


def _kernel(d_ref, khi_ref, klo_ref, ohi_ref, olo_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        ohi_ref[...] = jnp.zeros_like(ohi_ref)
        olo_ref[...] = jnp.zeros_like(olo_ref)

    d_cols = d_ref[...].T                        # (S, ROWS) digit columns
    k_hi = khi_ref[...]                          # (S, T)
    k_lo = klo_ref[...]
    for b in range(ROWS):
        blk_hi, blk_lo = _row_mac(d_cols[:, b:b + 1], k_hi, k_lo)
        old_lo = olo_ref[b, :]
        acc_lo = old_lo + blk_lo
        olo_ref[b, :] = acc_lo
        ohi_ref[b, :] = (ohi_ref[b, :] + blk_hi
                         + (acc_lo < old_lo).astype(U32))


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def keyswitch_mac(digits: jax.Array, ksk_hi: jax.Array, ksk_lo: jax.Array, *,
                  block_s: int = 512, interpret: bool | None = None):
    """digits (B, S) int32, ksk_hi/lo (S, T) uint32 -> (hi, lo) (B, T) uint32.

    S flattens (n_from * level); T = n_to + 1.  When S is not a multiple
    of the block size, digits and key rows are zero-padded up to one —
    zero digits contribute nothing to the MAC, so the result is
    unchanged (the fused engine path hits this whenever
    big_n * ks_level is not block-aligned).  The batch is zero-padded
    to a multiple of ROWS: each grid step MACs ROWS ciphertexts against
    one key block, an (8, 128)-tiled digit block.
    """
    B, S = digits.shape
    _, T = ksk_hi.shape
    bs = min(block_s, S)
    pad = (-S) % bs
    if pad:
        zeros_k = jnp.zeros((pad, T), dtype=ksk_hi.dtype)
        digits = jnp.pad(digits, ((0, 0), (0, pad)))
        ksk_hi = jnp.concatenate([ksk_hi, zeros_k], axis=0)
        ksk_lo = jnp.concatenate([ksk_lo, zeros_k], axis=0)
        S += pad
    digits = jnp.pad(digits, ((0, (-B) % ROWS), (0, 0)))
    Bp = digits.shape[0]
    assert S % bs == 0 and bs <= 4096
    z = np.int32(0)  # int32 block indices, also under jax_enable_x64
    out_shape = [
        jax.ShapeDtypeStruct((Bp, T), U32),
        jax.ShapeDtypeStruct((Bp, T), U32),
    ]
    hi, lo = pl.pallas_call(
        _kernel,
        out_shape=out_shape,
        grid=(Bp // ROWS, S // bs),
        in_specs=[
            pl.BlockSpec((ROWS, bs), lambda b, s: (b, s)),
            pl.BlockSpec((bs, T), lambda b, s: (s, z)),
            pl.BlockSpec((bs, T), lambda b, s: (s, z)),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, T), lambda b, s: (b, z)),
            pl.BlockSpec((ROWS, T), lambda b, s: (b, z)),
        ],
        interpret=interpret_mode() if interpret is None else interpret,
    )(digits.astype(jnp.int32), ksk_hi, ksk_lo)
    return hi[:B], lo[:B]
