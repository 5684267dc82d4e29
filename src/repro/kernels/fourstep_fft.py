"""Four-step negacyclic FFT as MXU matmuls (paper §IV-C, adapted to TPU).

The paper factors its 2^15-point double-real FFT into heterogeneous
256-point (FFT-A) and 128-point (FFT-B) units joined by a shutter
transpose.  On TPU the same factorization M = R*C maps onto the MXU:

    stage A:  DFT_R  @ X      (column transforms — one matmul)
    twiddle:  elementwise W^(k1*c)
    stage B:  X @ DFT_C^T     (row transforms — one matmul)

The shutter-transpose becomes the (free) matmul operand layout change.
Complex arithmetic is carried as separate re/im planes (stacked axis),
i.e. 4 real matmuls per complex matmul.

Precision: the kernel is dtype-polymorphic.  f32 is the TPU-native
plane dtype (benchmark/standalone mode; relative error ~2e-5 of the
spectrum scale).  The fused PBS engine path (`repro.kernels.fused_pbs`)
runs the SAME kernel with f64 planes — interpret mode executes f64
natively, and the scheme's noise budget needs the f64 accuracy for
64-bit torus operands (a hardware TPU deployment would swap in the
split-plane fixed-point path of the paper's Obs. 4 instead).

Layout contract (matches `repro.core.fft` up to dtype):
    forward:  real coeffs (B, N) -> spectrum (B, 2, M), M = N/2,
              spectrum[m] = FFT_M(fold+twist(x))[m]
    inverse:  spectrum (B, 2, M) -> real coeffs (B, N)

Tiling: the wrappers fold each polynomial into (2, R, C) planes and the
spectrum's natural-order transpose happens outside the kernel, so every
block is (ROWS, 2, R, C) with full-extent minor dims, and each grid
step transforms ROWS = 8 polynomials (the batch is zero-padded up to a
multiple of 8).  No in-kernel reshape or transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.fft import dft_constants, factor_m  # noqa: F401
from repro.kernels import interpret_mode

ROWS = 8  # polynomials per grid step: the sublane tile of the batch axis


def _planes(N: int, inverse: bool, dtype):
    """Kernel constants in the plane dtype: twist (2, R, C), DFT_R
    (2, R, R), DFT_C (2, C, C), twiddle (2, R, C)."""
    R, C, twist, dr, dc, tw = dft_constants(N, inverse)
    cast = lambda a: a.astype(dtype)
    return R, C, cast(twist.reshape(2, R, C)), cast(dr), cast(dc), cast(tw)


def _cmatmul(ar, ai, br, bi, acc_dtype):
    """(ar+i*ai) @ (br+i*bi) with plane-dtype accumulation on the MXU."""
    dot = lambda x, y: jnp.dot(x, y, preferred_element_type=acc_dtype)
    return dot(ar, br) - dot(ai, bi), dot(ar, bi) + dot(ai, br)


def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _fwd_kernel(x_ref, twist_ref, dr_ref, dc_ref, tw_ref, o_ref, *,
                acc_dtype):
    for b in range(ROWS):
        # fold + twist: u = (x_lo + i x_hi) * twist, as (R, C) planes
        ur, ui = _cmul(x_ref[b, 0], x_ref[b, 1], twist_ref[0], twist_ref[1])
        # stage A (FFT-A analogue): column DFT via MXU
        er, ei = _cmatmul(dr_ref[0], dr_ref[1], ur, ui, acc_dtype)
        # twiddle (between-stage rotation)
        br, bi = _cmul(er, ei, tw_ref[0], tw_ref[1])
        # stage B (FFT-B analogue): row DFT; the paper's shutter
        # transpose is the wrapper's store layout
        fr, fi = _cmatmul(br, bi, dc_ref[0], dc_ref[1], acc_dtype)
        o_ref[b, 0] = fr
        o_ref[b, 1] = fi


def _inv_kernel(s_ref, twist_ref, dr_ref, dc_ref, tw_ref, o_ref, *,
                acc_dtype):
    for b in range(ROWS):
        # inverse stage B, un-twiddle, inverse stage A
        br, bi = _cmatmul(s_ref[b, 0], s_ref[b, 1], dc_ref[0], dc_ref[1],
                          acc_dtype)
        er, ei = _cmul(br, bi, tw_ref[0], tw_ref[1])
        ar, ai = _cmatmul(dr_ref[0], dr_ref[1], er, ei, acc_dtype)
        # untwist; the wrapper unfolds (re, im) into coefficients
        xr, xi = _cmul(ar, ai, twist_ref[0], twist_ref[1])
        o_ref[b, 0] = xr
        o_ref[b, 1] = xi


def _call(kernel, planes, consts, interpret):
    Bp, _, R, C = planes.shape
    z = np.int32(0)  # int32 block indices, also under jax_enable_x64
    rows = pl.BlockSpec((ROWS, 2, R, C), lambda b: (b, z, z, z))
    full = lambda a: pl.BlockSpec(a.shape, lambda b: (z,) * a.ndim)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(planes.shape, planes.dtype),
        grid=(Bp // ROWS,),
        in_specs=[rows] + [full(c) for c in consts],
        out_specs=rows,
        interpret=interpret_mode() if interpret is None else interpret,
    )(planes, *consts)


def _pad_rows(x: jax.Array) -> jax.Array:
    pad = (-x.shape[0]) % ROWS
    if not pad:
        return x
    return jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])


@functools.partial(jax.jit, static_argnames=("interpret", "dtype"))
def fft_forward(x: jax.Array, *, interpret: bool | None = None,
                dtype=jnp.float32) -> jax.Array:
    """Negacyclic forward transform: real (B, N) -> (B, 2, N/2) planes."""
    B, N = x.shape
    M = N // 2
    dtype = jnp.dtype(dtype)
    R, C, *consts = _planes(N, False, dtype)
    kernel = functools.partial(_fwd_kernel, acc_dtype=dtype)
    planes = _pad_rows(x.astype(dtype)).reshape(-1, 2, R, C)
    spec = _call(kernel, planes, consts, interpret)      # [k1, k2] order
    return jnp.swapaxes(spec, -1, -2).reshape(-1, 2, M)[:B]


@functools.partial(jax.jit, static_argnames=("interpret", "dtype"))
def fft_inverse(spec: jax.Array, *, interpret: bool | None = None,
                dtype=jnp.float32) -> jax.Array:
    """Inverse: (B, 2, M) planes -> real coeffs (B, 2M)."""
    B, _, M = spec.shape
    N = 2 * M
    dtype = jnp.dtype(dtype)
    R, C, *consts = _planes(N, True, dtype)
    kernel = functools.partial(_inv_kernel, acc_dtype=dtype)
    planes = jnp.swapaxes(
        _pad_rows(spec.astype(dtype)).reshape(-1, 2, C, R), -1, -2)
    return _call(kernel, planes, consts, interpret).reshape(-1, N)[:B]
