"""Negacyclic polynomial multiplication over real re/im planes.

The paper (§IV-C) processes a degree-2^16 polynomial with a 2^15-point
complex FFT ("double-real FFT").  This module is the mathematical core of
that trick in plain JAX, written in the types a TPU compiles:

    forward :  N real coeffs  ->  N/2 complex values, as (2, N/2) planes
               u_j = a_j + i * a_{j+N/2}
               v_j = u_j * exp(i*pi*j/N)            (the "twist")
               A   = DFT_{N/2}(v)                   (four-step, matmuls)
    pointwise multiply in the transform domain == negacyclic convolution
    inverse :  untwist + split real/imag.

Complex values never appear as a dtype: every spectrum is a float64
array with a stacked (re, im) axis just before the coefficient axis,
(..., 2, N/2).  The DFT of size M = N/2 is the four-step factorisation
M = R*C as two real-plane matmul stages joined by a twiddle, the same
split the Pallas kernel `repro.kernels.fourstep_fft` makes; spectra come
out in natural order so the two agree element for element.  XLA:TPU
refuses complex128 (and aborts on some of it), but compiles float64
elementwise ops and `dot`, which it emulates at about 46 bits of
relative precision: ample for the external product, whose operands are
gadget digits times torus values (error budget in
`tests/test_core_fft.py`).

`negacyclic_mul_exact` is the exact variant for keys: the torus operand
is split into 16-bit limbs, so every limb product is an integer far
below 2^53 that the transform returns exactly after rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import torus

LIMB_BITS = 16


def factor_m(M: int) -> tuple[int, int]:
    """Pick R*C = M mirroring the paper's 256x128 for M = 2^15."""
    assert M & (M - 1) == 0 and M >= 4
    lg = M.bit_length() - 1
    r = min(256, 1 << ((lg + 1) // 2))
    return r, M // r


@functools.lru_cache(maxsize=32)
def dft_constants(N: int, inverse: bool):
    """Twist, DFT_R, DFT_C and twiddle as float64 (2, ...) numpy planes.

    Cached as numpy, never jnp: a jnp constant made inside a jit trace is
    a Tracer and would leak through the cache.  DFT matrices are
    symmetric, so `x @ D` is the transform along the last axis.
    """
    M = N // 2
    R, C = factor_m(M)
    # angles reduced mod 2*pi in integers first: exp() of a large angle
    # loses ~log2(angle) bits, which the 64-bit torus cannot spare
    root = lambda a, b, n: np.exp(-2j * np.pi * (np.outer(a, b) % n) / n)
    twist = np.exp(1j * np.pi * np.arange(M) / N)
    dft_r = root(np.arange(R), np.arange(R), R)
    dft_c = root(np.arange(C), np.arange(C), C)
    tw = root(np.arange(R), np.arange(C), M)
    if inverse:
        twist, dft_r, dft_c, tw = (np.conj(twist), np.conj(dft_r) / R,
                                   np.conj(dft_c) / C, np.conj(tw))
    planes = lambda z: np.stack([z.real, z.imag]).astype(np.float64)
    return R, C, planes(twist), planes(dft_r), planes(dft_c), planes(tw)


def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _left(d, xr, xi):
    """Complex D @ X along axis -2 of (..., R, C) planes."""
    mm = lambda a, b: jnp.einsum("kr,...rc->...kc", a, b)
    return mm(d[0], xr) - mm(d[1], xi), mm(d[1], xr) + mm(d[0], xi)


def _right(xr, xi, d):
    """Complex X @ D along the last axis of (..., R, C) planes."""
    return xr @ d[0] - xi @ d[1], xr @ d[1] + xi @ d[0]


def forward(poly: jax.Array) -> jax.Array:
    """Real (..., N) -> float64 planes (..., 2, N/2) negacyclic spectrum.

    Accepts float64 or (u)int coefficient arrays; integers are taken as
    SIGNED representatives (int64 view for torus values).
    """
    N = poly.shape[-1]
    M = N // 2
    R, C, twist, dr, dc, tw = dft_constants(N, False)
    if jnp.issubdtype(poly.dtype, jnp.unsignedinteger):
        poly = torus.to_signed(poly)
    x = poly.astype(jnp.float64)
    lead = x.shape[:-1]
    ur, ui = _cmul(x[..., :M], x[..., M:], twist[0], twist[1])
    er, ei = _left(dr, ur.reshape(*lead, R, C), ui.reshape(*lead, R, C))
    br, bi = _cmul(er, ei, tw[0], tw[1])
    fr, fi = _right(br, bi, dc)
    # natural order m = k1 + R*k2: flatten the (k2, k1) transpose
    spec = jnp.stack([fr, fi], axis=-3)                  # (..., 2, R, C)
    return jnp.swapaxes(spec, -1, -2).reshape(*lead, 2, M)


def inverse(spec: jax.Array) -> jax.Array:
    """Planes (..., 2, N/2) -> float64 (..., N) coefficients."""
    M = spec.shape[-1]
    N = 2 * M
    R, C, twist, dr, dc, tw = dft_constants(N, True)
    lead = spec.shape[:-2]
    s = jnp.swapaxes(spec.reshape(*lead, 2, C, R), -1, -2)   # (..., 2, R, C)
    br, bi = _right(s[..., 0, :, :], s[..., 1, :, :], dc)
    er, ei = _cmul(br, bi, tw[0], tw[1])
    ar, ai = _left(dr, er, ei)
    ur, ui = _cmul(ar.reshape(*lead, M), ai.reshape(*lead, M),
                   twist[0], twist[1])
    return jnp.concatenate([ur, ui], axis=-1)


def inverse_torus(spec: jax.Array) -> jax.Array:
    """Inverse transform folded back onto the torus (uint64 mod 2^64)."""
    return torus.float_to_torus(inverse(spec))


def mul(a: jax.Array, b: jax.Array) -> jax.Array:
    """Pointwise complex product of two (..., 2, M) spectra."""
    re, im = _cmul(a[..., 0, :], a[..., 1, :], b[..., 0, :], b[..., 1, :])
    return jnp.stack([re, im], axis=-2)


def mac(dig_f: jax.Array, key_f: jax.Array) -> jax.Array:
    """Transform-domain vector-matrix product (the BRU MAC).

    dig_f (..., J, 2, M) spectra of J decomposition rows; key_f
    (2, J, K, M) one GGSW in plane layout -> (..., K, 2, M), summed over
    J.  A broadcast multiply-reduce: J is a handful of rows, so this is
    elementwise work on the coefficient axis, not a matmul.
    """
    dr, di = dig_f[..., 0, :], dig_f[..., 1, :]            # (..., J, M)
    kr, ki = key_f[0], key_f[1]                            # (J, K, M)
    dot = lambda d, k: (d[..., :, None, :] * k).sum(axis=-3)
    re = dot(dr, kr) - dot(di, ki)
    im = dot(dr, ki) + dot(di, kr)
    return jnp.stack([re, im], axis=-2)


def negacyclic_mul(a: jax.Array, b: jax.Array) -> jax.Array:
    """Exact-ish negacyclic product of two integer polys, mod 2^64.

    `a` is expected to hold SMALL integers (e.g. gadget-decomposed digits),
    `b` arbitrary torus values; this keeps the f64 roundoff below the
    scheme noise (the paper's 48-bit fixed-point argument, Obs. 4).
    """
    return inverse_torus(mul(forward(a), forward(b)))


def negacyclic_mul_exact(small: jax.Array, tor: jax.Array) -> jax.Array:
    """sum_i small_i * tor_i (negacyclic), EXACT mod 2^64.

    small (k, N) integers with N * max|small| * 2^16 far below 2^53
    (binary secret keys); tor (..., k, N) uint64 torus polynomials.
    Each 16-bit limb of `tor` gives an integer product that the f64
    transform returns exactly after rounding; the limbs recombine by
    shifts in uint64.  -> (..., N) uint64.
    """
    s_f = forward(small)                                   # (k, 2, M)
    shifts = jnp.arange(0, 64, LIMB_BITS, dtype=jnp.uint64)
    limbs = (tor[..., None, :, :] >> shifts[:, None, None]) & jnp.uint64(
        (1 << LIMB_BITS) - 1)                              # (..., L, k, N)
    prod = mul(forward(limbs), s_f).sum(axis=-3)           # (..., L, 2, M)
    vals = jnp.round(inverse(prod)).astype(jnp.int64).astype(jnp.uint64)
    return (vals << shifts[:, None]).sum(axis=-2, dtype=jnp.uint64)
