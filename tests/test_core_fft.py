import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fft, torus

U64 = jnp.uint64


def naive_negacyclic(a, b):
    N = a.shape[0]
    c = np.zeros(N, dtype=object)
    for i in range(N):
        for j in range(N):
            k = i + j
            if k < N:
                c[k] += int(a[i]) * int(b[j])
            else:
                c[k - N] -= int(a[i]) * int(b[j])
    return np.array([x % (1 << 64) for x in c], dtype=np.uint64)


@pytest.mark.parametrize("N", [64, 256, 1024])
def test_negacyclic_mul_small_ints(N):
    rng = np.random.default_rng(N)
    a = rng.integers(-128, 128, N)
    b = rng.integers(-128, 128, N)
    ref = naive_negacyclic(a, b)
    got = fft.negacyclic_mul(
        jnp.asarray(a, dtype=jnp.int64), jnp.asarray(b, dtype=jnp.int64)
    )
    np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.parametrize("N", [256, 1024])
def test_negacyclic_mul_digit_by_torus(N):
    """digits (small) x torus (uint64) — the external-product regime.

    f64 roundoff must stay far below the scheme noise slot (the 48-bit
    fixed-point argument, Obs. 4).  Expected floor: terms ~ B*2^63, summed
    over N with log(N) FFT stages -> ~ N * B * 2^64 * 2^-53 absolute.
    For width<=6 the message slot is >= 2^57, so a 2^28 bound leaves
    >= 29 bits of headroom.
    """
    rng = np.random.default_rng(N + 1)
    a = rng.integers(-(1 << 7), 1 << 7, N)                 # decomposed digits
    b = rng.integers(0, 1 << 64, N, dtype=np.uint64)       # torus values
    ref = naive_negacyclic(a, b)
    got = np.asarray(fft.negacyclic_mul(
        jnp.asarray(a, dtype=jnp.int64), jnp.asarray(b, dtype=U64)
    ))
    err = (got - ref).astype(np.int64)  # wraparound-aware difference
    assert np.max(np.abs(err)) < (1 << 28)


def test_forward_inverse_roundtrip():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-1000, 1000, 512), dtype=jnp.float64)
    back = fft.inverse(fft.forward(a))
    np.testing.assert_allclose(np.asarray(back), np.asarray(a), atol=1e-6)


@pytest.mark.parametrize("N", [64, 256, 1024])
def test_fourstep_parity_with_core_fft(N):
    """The MXU four-step factorization must agree with `repro.core.fft`
    (the engine's reference transform) in BOTH directions: same spectrum
    layout forward, and forward∘inverse returning the input."""
    from repro.kernels import fourstep_fft

    rng = np.random.default_rng(N)
    x = rng.integers(-(1 << 10), 1 << 10, (3, N)).astype(np.float32)
    spec = fourstep_fft.fft_forward(jnp.asarray(x))         # (B, 2, N/2) f32
    ref = np.asarray(fft.forward(jnp.asarray(x, jnp.float64)))  # f64 planes
    scale = float(np.abs(ref).max()) + 1.0
    np.testing.assert_allclose(
        np.asarray(spec[:, 0]) / scale, ref[:, 0] / scale, atol=3e-5)
    np.testing.assert_allclose(
        np.asarray(spec[:, 1]) / scale, ref[:, 1] / scale, atol=3e-5)
    back = fourstep_fft.fft_inverse(spec)
    np.testing.assert_allclose(np.asarray(back), x, atol=scale * 3e-5)


def test_float_to_torus_wraps():
    # inputs chosen to be exactly representable in f64
    x = jnp.asarray(
        [0.0, 1.0, -1.0, 2.0**64, 2.0**33 + 7, -(2.0**33) - 3, 2.0**64 + 2.0**20],
        dtype=jnp.float64,
    )
    got = np.asarray(torus.float_to_torus(x))
    expect = np.array(
        [0, 1, (1 << 64) - 1, 0, (1 << 33) + 7, (1 << 64) - (1 << 33) - 3, 1 << 20],
        dtype=np.uint64,
    )
    np.testing.assert_array_equal(got, expect)
