"""The external product as exact int8 matmuls (the TPU's engine room).

The CMux step's external product is a negacyclic product of gadget
digits (signed, at most base_log bits) with GGSW polynomials (64-bit
torus values), summed over the J = (k+1)*level decomposition rows.  This
module computes it exactly mod 2^64 as int8 x int8 -> int32 matmuls,
which the TPU's MXU runs natively, instead of through a float64
transform, which XLA:TPU emulates.

Block-negacyclic layout.  Cut N into nb = N/b blocks of b coefficients.
The negacyclic matrix of a polynomial S has, at (input block u, output
block w), the block +A_o when w >= u and -A_o when w < u, o = (w-u) mod
nb, where

    A_o[t, s] = S~[b*o + s - t],   S~[x] = S[x] (x >= 0), -S[N+x] (x < 0).

So only the nb distinct blocks A_o are stored per (row j, output poly
k).  The digits carry the sign and the roll instead: with
d~[u] = d[u] for u >= 0 and -d[u+nb] for u < 0,

    out[w] = sum_{o, j} d~_j[w - o] @ A_{o,j}.

Per CMux step the key is one matrix Y with rows (o', j, t) and columns
(c, k, s), o' = nb-1-o, stored as KEY_LIMBS balanced int8 limbs c of the
torus values; the digits become X with rows (batch, a, w) and columns
(o', j, t), as digit limbs a, whose rows are windows of d~ (the
reversed o' makes each a contiguous slice).  X @ Y is thus a
correlation of d~'s limbs with Y over the block axis (`correlate`): one
convolution on a TPU, a matmul over the stacked windows elsewhere.  It
gives every limb pair, and the pairs recombine with shifts 2^(8(a+c))
in uint64, keeping only a + c < 8.  The sum is exact when every grouped
int32 partial stays below 2^31 (`exact`).

b is the smallest block with K*b a multiple of the MXU's 128 lanes:
64 for k = 1.  The matmul costs B*A*KEY_LIMBS*N^2*J*K MACs whatever b
is; the key holds N*J*KEY_LIMBS*K*b bytes per step (4 MiB at N = 2048,
k = 1, level 1).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

U64 = jnp.uint64
KEY_LIMBS = 8            # balanced int8 limbs of a 64-bit torus value
MXU_LANES = 128
MAX_N = 4096             # above it the key outgrows a 16 GB chip


def block_size(N: int, K: int) -> int:
    return min(N, MXU_LANES // math.gcd(K, MXU_LANES))


def digit_limbs(base_log: int) -> int:
    """Balanced int8 limbs that hold a digit in [-2^(bl-1), 2^(bl-1)]
    (A limbs reach 127 * (256^A - 1) / 255, just short of 2^(8A-1))."""
    return base_log // 8 + 1


def exact(N: int, J: int, pairs: int = KEY_LIMBS) -> bool:
    """Whether int32 holds a shift's grouped sum: N*J terms of at most
    128*128 per limb pair, and at most `pairs` limb pairs per shift."""
    return N * J * pairs * (1 << 14) < (1 << 31)


def balanced_limbs(x: jax.Array, count: int, axis: int) -> jax.Array:
    """int8 limbs l_c in [-128, 127], stacked at `axis`, with
    x = sum_c 256^c l_c (mod 2^64 for uint64 x; exactly for a signed x
    that `count` limbs can hold)."""
    one = x.dtype.type
    out = []
    for _ in range(count):
        y = x + one(128)
        out.append(((y & one(255)).astype(jnp.int32) - 128).astype(jnp.int8))
        x = y >> one(8)
    return jnp.stack(out, axis=axis)


def ggsw_to_blocks(ggsw_ct: jax.Array) -> jax.Array:
    """One GGSW (k+1, level, k+1, N) uint64 -> Y, (N*J, KEY_LIMBS*K*b)
    int8 (module docstring)."""
    kp1, level, K, N = ggsw_ct.shape
    J = kp1 * level
    b = block_size(N, K)
    nb = N // b
    S = ggsw_ct.reshape(J, K, N)
    ext = jnp.concatenate([-S[..., N - b:], S], axis=-1)    # ext[b+x] = S~[x]
    o_rev, t, s = np.ogrid[:nb, :b, :b]
    A = ext[..., b + b * (nb - 1 - o_rev) + s - t]           # (J, K, o', t, s)
    A = A.transpose(2, 0, 3, 1, 4)                           # (o', J, t, K, s)
    limbs = balanced_limbs(A, KEY_LIMBS, axis=3)             # (o', J, t, c, K, s)
    return limbs.reshape(N * J, KEY_LIMBS * K * b)


def bsk_to_blocks(bsk: jax.Array) -> jax.Array:
    """(n, k+1, level, k+1, N) uint64 BSK -> (n, N*J, KEY_LIMBS*K*b)
    int8, one GGSW at a time so that the uint64 blocks of the whole key
    never exist at once."""
    return jax.lax.map(ggsw_to_blocks, bsk)


def correlate_conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """z[n, w] = sum_i x[n, w + i] @ kernel[i] in int32: x (R, 2nb-1, F)
    int8, kernel (nb, F, C) int8 -> (R, nb, C), as one convolution, which
    the TPU's MXU runs without forming the windows."""
    return jax.lax.conv_general_dilated(
        x, kernel, window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        preferred_element_type=jnp.int32)


def correlate_windows(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """`correlate_conv` as one matmul over the stacked windows, for
    XLA:CPU, whose integer convolution is hundreds of times slower."""
    R, _, F = x.shape
    nb, _, C = kernel.shape
    win = jnp.stack([x[:, w:w + nb] for w in range(nb)], axis=1)
    z = jnp.dot(win.reshape(R * nb, nb * F), kernel.reshape(nb * F, C),
                preferred_element_type=jnp.int32)
    return z.reshape(R, nb, C)


def correlate(x: jax.Array, kernel: jax.Array) -> jax.Array:
    if jax.default_backend() == "tpu":
        return correlate_conv(x, kernel)
    return correlate_windows(x, kernel)


def external_product(blocks: jax.Array, digits: jax.Array, K: int,
                     base_log: int) -> jax.Array:
    """sum_j digits_j * GGSW_{j,k} (negacyclic), exact mod 2^64.

    blocks (N*J, KEY_LIMBS*K*b) int8 from `ggsw_to_blocks`; digits
    (..., J, N) signed integers in [-2^(base_log-1), 2^(base_log-1)]
    -> (..., K, N) uint64.
    """
    *lead, J, N = digits.shape
    B = math.prod(lead)
    b = block_size(N, K)
    nb = N // b
    A = digit_limbs(base_log)
    if not exact(N, J, min(A, KEY_LIMBS)):
        raise ValueError(f"N={N}, J={J}, {A} digit limbs overflow int32")
    with jax.named_scope("digit_limbs"):
        # (batch, block, row j and offset t): the last axis J*b lanes wide
        d = digits.astype(jnp.int32).reshape(B, J, nb, b).swapaxes(1, 2)
        d = d.reshape(B, nb, J * b)
        ext = jnp.concatenate([-d, d], axis=1)        # ext[nb+u] = d~[u]
        limbs = balanced_limbs(ext[:, 1:], A, axis=1)  # (B, A, 2nb-1, J*b)
    with jax.named_scope("int8_matmul"):        # X @ Y
        z = correlate(limbs.reshape(B * A, 2 * nb - 1, J * b),
                      blocks.reshape(nb, J * b, KEY_LIMBS * K * b))
    with jax.named_scope("recombine"):
        z = z.reshape(B, A, nb, KEY_LIMBS, K * b)
        # g[sigma] = sum over a + c = sigma of the (a, c) partials, c < 8
        g = sum(jnp.pad(z[:, a], ((0, 0), (0, 0), (a, 0), (0, 0)))
                [:, :, :KEY_LIMBS] for a in range(A))  # (B, nb, 8, K*b)
        shifts = U64(8) * jnp.arange(KEY_LIMBS, dtype=U64)[:, None]
        out = (g.astype(jnp.int64).astype(U64) << shifts).sum(axis=2,
                                                              dtype=U64)
        return out.reshape(B, nb, K, b).swapaxes(1, 2).reshape(*lead, K, N)
