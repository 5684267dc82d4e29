"""BRU transform-domain MAC kernel (paper Fig. 7 bottom).

Computes, for a whole round-robin batch of ciphertexts against ONE shared
BSK slice (the key-reuse strategy):

    out[b, k, f] = sum_j dig[b, j, f] * bsk[j, k, f]        (complex)

with j = (k_dim+1)*level decomposition rows, k = k_dim+1 output polys,
f the transform-domain coefficient.  The BSK block is loaded into VMEM
once per grid step and consumed by every ciphertext in the batch —
arithmetic intensity on the BSK stream scales with B, which is exactly
why Taurus round-robins 12 ciphertexts per core.

Layouts (stacked re/im planes, f32 or f64 via `dtype`):
    dig: (B, 2, J, F)     bsk: (2, J, K, F)     out: (B, 2, K, F)
The grid tiles F (VMEM-sized chunks, multiples of 128 lanes).  The
fused PBS engine (`repro.kernels.fused_pbs`) keeps the BSK operand
RESIDENT in this transform-domain plane layout across every round of a
fused batch — the decomposition + transform is paid once per key, not
once per round.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(dig_ref, bsk_ref, o_ref):
    # dig (2, J, B, Fb), bsk (2, J, K, Fb), out (2, K, B, Fb): every
    # operand below is a (B, Fb) tile or a (1, Fb) key row broadcast
    # over the batch, so the MAC is J*K elementwise multiply-adds
    J, K = bsk_ref.shape[1], bsk_ref.shape[2]
    for k in range(K):
        out_r = out_i = None
        for j in range(J):
            dr, di = dig_ref[0, j], dig_ref[1, j]
            wr = bsk_ref[0, j, pl.ds(k, 1), :]
            wi = bsk_ref[1, j, pl.ds(k, 1), :]
            r, i = dr * wr - di * wi, dr * wi + di * wr
            out_r = r if out_r is None else out_r + r
            out_i = i if out_i is None else out_i + i
        o_ref[0, k] = out_r
        o_ref[1, k] = out_i


@functools.partial(jax.jit, static_argnames=("block_f", "interpret", "dtype"))
def external_product_mac(dig: jax.Array, bsk: jax.Array, *,
                         block_f: int = 512, interpret: bool | None = None,
                         dtype=jnp.float32) -> jax.Array:
    """dig (B,2,J,F), bsk (2,J,K,F) -> (B,2,K,F), stacked re/im planes.

    `dtype` selects the plane precision: f32 is the TPU-native mode; the
    fused engine path runs f64 planes (interpret mode) so the MAC error
    stays far below the scheme's noise budget on 64-bit torus operands.
    The batch axis moves next to F outside the kernel, so each grid
    step's tiles are (B, block_f) and fit VMEM at any J, K.
    """
    B, _, J, F = dig.shape
    _, _, K, _ = bsk.shape
    dtype = jnp.dtype(dtype)
    bf = min(block_f, F)
    assert F % bf == 0
    z = np.int32(0)  # int32 block indices, also under jax_enable_x64
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((2, K, B, F), dtype),
        grid=(F // bf,),
        in_specs=[
            pl.BlockSpec((2, J, B, bf), lambda f: (z, z, z, f)),
            pl.BlockSpec((2, J, K, bf), lambda f: (z, z, z, f)),
        ],
        out_specs=pl.BlockSpec((2, K, B, bf), lambda f: (z, z, z, f)),
        interpret=interpret_mode() if interpret is None else interpret,
    )(jnp.transpose(dig.astype(dtype), (1, 2, 0, 3)), bsk.astype(dtype))
    return jnp.transpose(out, (2, 0, 1, 3))
