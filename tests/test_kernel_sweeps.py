"""Pallas kernel sweeps: shapes x dtypes against the ref.py oracles
(interpret mode on CPU; deliverable c)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels import fourstep_fft, external_product, keyswitch


@pytest.mark.parametrize("N", [256, 1024, 4096, 16384])
@pytest.mark.parametrize("B", [1, 3])
def test_fourstep_fft_roundtrip_sweep(N, B):
    rng = np.random.default_rng(N + B)
    x = rng.integers(-2 ** 20, 2 ** 20, (B, N)).astype(np.float32)
    spec = fourstep_fft.fft_forward(jnp.asarray(x))
    ref_spec = ref.fft_forward_ref(jnp.asarray(x, jnp.float64))
    scale = np.abs(np.asarray(ref_spec)).max()
    np.testing.assert_allclose(np.asarray(spec), np.asarray(ref_spec),
                               atol=scale * 2e-5, rtol=0)
    back = fourstep_fft.fft_inverse(spec)
    np.testing.assert_allclose(np.asarray(back), x, atol=scale * 2e-5)


@pytest.mark.parametrize("J,K,F", [(2, 2, 256), (4, 2, 512), (8, 4, 1024)])
@pytest.mark.parametrize("B", [1, 12])
def test_external_product_mac_sweep(J, K, F, B):
    rng = np.random.default_rng(J * K + F + B)
    dig = rng.normal(size=(B, 2, J, F)).astype(np.float32) * 100
    bsk = rng.normal(size=(2, J, K, F)).astype(np.float32)
    got = external_product.external_product_mac(jnp.asarray(dig),
                                                jnp.asarray(bsk),
                                                block_f=min(256, F))
    want = ref.external_product_mac_ref(jnp.asarray(dig, jnp.float64),
                                        jnp.asarray(bsk, jnp.float64))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-2)


@pytest.mark.parametrize("S,T", [(64, 65), (256, 129), (1024, 97)])
@pytest.mark.parametrize("B", [1, 5])
def test_keyswitch_mac_exact_sweep(S, T, B):
    """The limb kernel is EXACT mod 2^64 — bit-equal to the u64 oracle."""
    rng = np.random.default_rng(S + T + B)
    digits = rng.integers(-2 ** 15, 2 ** 15, (B, S)).astype(np.int32)
    ksk = rng.integers(0, 2 ** 64, (S, T), dtype=np.uint64)
    got = ops.lpu_keyswitch_mac(jnp.asarray(digits), jnp.asarray(ksk),
                                block_s=min(64, S))
    want = ref.keyswitch_mac_ref(jnp.asarray(digits), jnp.asarray(ksk))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-5),
                                        (jnp.float64, 1e-12)])
@pytest.mark.parametrize("N", [256, 2048, 8192])
def test_fourstep_fft_dtype_sweep(N, dtype, rtol):
    """The kernels are dtype-polymorphic: f32 (TPU-native) to ~2e-5 of
    the spectrum scale, f64 (fused engine path) to ~1e-12."""
    rng = np.random.default_rng(N)
    x = rng.integers(-2 ** 20, 2 ** 20, (2, N)).astype(np.float64)
    spec = fourstep_fft.fft_forward(jnp.asarray(x, dtype), dtype=dtype)
    assert spec.dtype == jnp.dtype(dtype)
    ref_spec = ref.fft_forward_ref(jnp.asarray(x, jnp.float64))
    scale = np.abs(np.asarray(ref_spec)).max()
    np.testing.assert_allclose(np.asarray(spec), np.asarray(ref_spec),
                               atol=scale * rtol, rtol=0)
    back = fourstep_fft.fft_inverse(spec, dtype=dtype)
    np.testing.assert_allclose(np.asarray(back), x, atol=scale * rtol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-2),
                                       (jnp.float64, 1e-9)])
@pytest.mark.parametrize("B", [1, 12])
def test_external_product_mac_dtype_sweep(B, dtype, tol):
    rng = np.random.default_rng(B)
    dig = rng.normal(size=(B, 2, 4, 512)).astype(np.float64) * 100
    bsk = rng.normal(size=(2, 4, 2, 512)).astype(np.float64)
    got = external_product.external_product_mac(
        jnp.asarray(dig, dtype), jnp.asarray(bsk, dtype),
        block_f=256, dtype=dtype)
    assert got.dtype == jnp.dtype(dtype)
    want = ref.external_product_mac_ref(jnp.asarray(dig), jnp.asarray(bsk))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=tol)


@pytest.mark.parametrize("width_fixture", ["2bit"])
@pytest.mark.parametrize("B", [3, 12])
def test_fused_pbs_sweep_batch_sizes(request, width_fixture, B):
    """Fused-path differential across batch sizes on real key material
    (the sweep-level view of the tests in test_kernels.py)."""
    ctx = request.getfixturevalue(f"ctx_{width_fixture}")
    eng_ref = request.getfixturevalue(f"engine_{width_fixture}")
    eng_pal = request.getfixturevalue(f"pallas_engine_{width_fixture}")
    from repro.core import glwe
    p = ctx.params
    key = jax.random.PRNGKey(B)
    msgs = np.arange(B) % p.plaintext_modulus
    cts = jnp.stack([ctx.encrypt(jax.random.fold_in(key, i), int(m))
                     for i, m in enumerate(msgs)])
    table = jnp.asarray([(2 * v) % p.plaintext_modulus
                         for v in range(p.plaintext_modulus)],
                        dtype=jnp.uint64)
    polys = jnp.broadcast_to(glwe.make_lut_poly(table, p), (B, p.N))
    dec_ref = [int(ctx.decrypt(v)) for v in eng_ref.lut_batch(cts, polys)]
    dec_pal = [int(ctx.decrypt(v)) for v in eng_pal.lut_batch(cts, polys)]
    assert dec_pal == dec_ref


def test_fft_f32_precision_supports_48bit_claim():
    """Observation 4: the paper's 48-bit fixed point <-> our split path.
    A single f32 four-step FFT roundtrip keeps relative error ~1e-6 of
    the spectrum scale; the scheme's noise budget at width<=10 needs
    ~2^-40 of the torus, met by the f64 planes the engine runs; the
    split-f32 TPU path is ROADMAP S2."""
    rng = np.random.default_rng(0)
    N = 4096
    x = rng.integers(-2 ** 30, 2 ** 30, (2, N)).astype(np.float64)
    spec = fourstep_fft.fft_forward(jnp.asarray(x, jnp.float32))
    back = fourstep_fft.fft_inverse(spec)
    rel = np.abs(np.asarray(back) - x).max() / np.abs(x).max()
    assert rel < 5e-5
