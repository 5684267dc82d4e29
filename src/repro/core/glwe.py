"""GLWE ciphertexts: the LUT carriers of programmable bootstrapping.

Layout: (..., k+1, N) uint64 = [A_1 .. A_k, B]; each row a polynomial in
Z_q[X]/(X^N+1).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import torus, fft
from repro.core.params import TFHEParams

U64 = jnp.uint64


def keygen(key: jax.Array, k: int, N: int) -> jax.Array:
    """Binary GLWE secret key: (k, N) uint64 in {0,1}."""
    return jax.random.bernoulli(key, 0.5, (k, N)).astype(U64)


def flatten_key(glwe_key: jax.Array) -> jax.Array:
    """The 'big' LWE key sample-extract produces ciphertexts under."""
    return glwe_key.reshape(-1)


def encrypt(key: jax.Array, sk: jax.Array, msg_poly: jax.Array, std: float) -> jax.Array:
    """Encrypt torus polynomial(s) (..., N) -> (..., k+1, N)."""
    k, N = sk.shape
    shape = msg_poly.shape[:-1]
    ka, ke = jax.random.split(key)
    a = torus.random_torus(ka, shape + (k, N))
    e = torus.gaussian_noise(ke, shape + (N,), std)
    # b = sum_i a_i * s_i + m + e  (negacyclic products, exact)
    b = fft.negacyclic_mul_exact(sk, a) + msg_poly + e
    return jnp.concatenate([a, b[..., None, :]], axis=-2)


def decrypt_phase(sk: jax.Array, ct: jax.Array) -> jax.Array:
    a, b = ct[..., :-1, :], ct[..., -1, :]
    return b - fft.negacyclic_mul_exact(sk, a)


def trivial(msg_poly: jax.Array, k: int) -> jax.Array:
    """Noiseless GLWE (A=0, B=m): how LUT accumulators start life."""
    z = jnp.zeros(msg_poly.shape[:-1] + (k, msg_poly.shape[-1]), dtype=U64)
    return jnp.concatenate([z, msg_poly[..., None, :].astype(U64)], axis=-2)


def rotate(ct: jax.Array, r: jax.Array, N: int) -> jax.Array:
    """Multiply every polynomial by the monomial X^r, r in [0, 2N).

    Negacyclic: X^N = -1.  Works on any (..., N) trailing-axis layout and
    traced r (per the blind-rotation loop).
    """
    r = jnp.asarray(r, dtype=jnp.uint32).astype(jnp.int64)
    j = jnp.arange(N, dtype=jnp.int64)
    src = (j - r) % (2 * N)              # exponent index in [0, 2N)
    neg = src >= N                        # second copy carries a minus sign
    idx = jnp.where(neg, src - N, src)
    vals = jnp.take(ct, idx, axis=-1)
    return jnp.where(neg, -vals, vals)


def sample_extract(ct: jax.Array) -> jax.Array:
    """Extract the constant coefficient as an LWE ciphertext (paper step D).

    (..., k+1, N) -> (..., k*N+1) under the flattened GLWE key.
    """
    *lead, kp1, N = ct.shape
    a_polys, b_poly = ct[..., :-1, :], ct[..., -1, :]
    # a'_{i*N + j} = A_i[0] if j == 0 else -A_i[N - j]
    rev = -a_polys[..., :, ::-1]                         # -A_i[N-1-j']
    a = jnp.concatenate(
        [a_polys[..., :, :1], rev[..., :, : N - 1]], axis=-1
    )  # [A_i[0], -A_i[N-1], ..., -A_i[1]]
    a = a.reshape(*lead, (kp1 - 1) * N)
    return jnp.concatenate([a, b_poly[..., :1]], axis=-1)


def make_lut_poly(table: jax.Array, params: TFHEParams) -> jax.Array:
    """Encode a plaintext LUT f: [0, 2^width) -> [0, 2^width) as the test
    polynomial V (torus coefficients), pre-rotated by half a slot so the
    rounding window is centred (standard Concrete construction).

    table: (2^width,) integer outputs.
    """
    N, width = params.N, params.width
    reps = N // (1 << width)
    vals = torus.encode(jnp.asarray(table, dtype=U64), params.delta)
    v = jnp.repeat(vals, reps)                            # (N,)
    # multiply by X^{-reps/2}: rotate by 2N - reps//2
    v = rotate(v, jnp.asarray(2 * N - reps // 2), N)
    return v


def make_lut_polys(tables: jax.Array, params: TFHEParams) -> jax.Array:
    """Batched `make_lut_poly`: (B, 2^width) integer tables -> (B, N)."""
    return jax.vmap(lambda t: make_lut_poly(t, params))(
        jnp.asarray(tables, dtype=U64))


# Process-wide test-polynomial cache, one entry per UNIQUE table row per
# parameter set.  A PBS round's (B, 2^width) table stack is almost always
# a tile of 2-3 distinct rows (msg/carry/status tables), and in the
# serving runtime every concurrent request re-derives the same rows —
# encoding each distinct row once and gathering beats re-encoding whole
# stacks (the eager per-row encode at N >= 2048 costs more than the PBS
# dispatch it feeds).  Bounded FIFO: table rows arrive from CLIENT
# programs, so an adversarial stream of all-distinct tables must not pin
# unbounded server memory (each row is an (N,) uint64, ~16KB at N=2048).
# Lookups/eviction are lock-guarded (serving workers are concurrent); the
# expensive encode itself runs outside the lock, so a race at worst
# re-encodes a row.
_ROW_POLY_CACHE: dict = {}
_ROW_POLY_CACHE_MAX = 4096
_ROW_POLY_LOCK = threading.Lock()
# observability: unique-row hits/misses per lookup plus evictions, so
# tests (and serving dashboards) can assert cross-context reuse
_ROW_POLY_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def row_poly_cache_stats() -> dict:
    """Snapshot of the process-wide LUT-poly cache counters."""
    with _ROW_POLY_LOCK:
        return dict(_ROW_POLY_STATS)


def clear_row_poly_cache() -> None:
    """Drop every cached row and reset the counters (test isolation)."""
    with _ROW_POLY_LOCK:
        _ROW_POLY_CACHE.clear()
        _ROW_POLY_STATS.update(hits=0, misses=0, evictions=0)


def _cache_put(key, poly) -> None:
    with _ROW_POLY_LOCK:
        while len(_ROW_POLY_CACHE) >= _ROW_POLY_CACHE_MAX:
            _ROW_POLY_CACHE.pop(next(iter(_ROW_POLY_CACHE)), None)
            _ROW_POLY_STATS["evictions"] += 1
        _ROW_POLY_CACHE[key] = poly


def make_lut_polys_cached(tables, params: TFHEParams) -> jax.Array:
    """`make_lut_polys` through the process-wide per-row cache: only rows
    never seen under `params` are encoded; the stack is gathered from
    cached (N,) polynomials.  Safe under concurrent callers (a race at
    worst re-encodes a row)."""
    tables = np.ascontiguousarray(np.asarray(tables, dtype=np.uint64))
    row_keys = [r.tobytes() for r in tables]
    order: dict = {}
    for i, k in enumerate(row_keys):
        if k not in order:
            order[k] = i
    # snapshot hits locally (under the lock) so concurrent eviction can't
    # race the gather below; counters are per UNIQUE row per lookup
    with _ROW_POLY_LOCK:
        local = {k: _ROW_POLY_CACHE[(params, k)] for k in order
                 if (params, k) in _ROW_POLY_CACHE}
        _ROW_POLY_STATS["hits"] += len(local)
        _ROW_POLY_STATS["misses"] += len(order) - len(local)
    missing = [k for k in order if k not in local]
    if missing:
        polys = make_lut_polys(
            np.stack([tables[order[k]] for k in missing]), params)
        for j, k in enumerate(missing):
            local[k] = polys[j]
            _cache_put((params, k), polys[j])
    uniq = jnp.stack([local[k] for k in order])
    slot = {k: j for j, k in enumerate(order)}
    return uniq[jnp.asarray([slot[k] for k in row_keys])]
