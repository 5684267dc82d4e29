"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Shape/dtype sweeps per the deliverable contract; tolerances account for
the f32 kernel vs f64 oracle gap.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.fourstep_fft import factor_m

jax.config.update("jax_enable_x64", True)


# --- four-step FFT -----------------------------------------------------------

@pytest.mark.parametrize("N", [256, 512, 2048, 8192, 65536])
@pytest.mark.parametrize("B", [1, 3])
def test_fft_forward_matches_ref(N, B):
    rng = np.random.default_rng(N + B)
    x = jnp.asarray(rng.integers(-(1 << 7), 1 << 7, (B, N)), dtype=jnp.float32)
    got = np.asarray(ops.negacyclic_fft(x))
    want = np.asarray(ref.fft_forward_ref(x))
    scale = np.max(np.abs(want)) + 1.0
    np.testing.assert_allclose(got / scale, want / scale, atol=2e-5)


@pytest.mark.parametrize("N", [256, 2048, 65536])
def test_fft_roundtrip(N):
    rng = np.random.default_rng(N)
    x = jnp.asarray(rng.integers(-(1 << 10), 1 << 10, (2, N)), dtype=jnp.float32)
    back = np.asarray(ops.negacyclic_ifft(ops.negacyclic_fft(x)))
    np.testing.assert_allclose(back, np.asarray(x), atol=0.25 * np.sqrt(N) / 8)


def test_fft_factorization_matches_paper():
    # the paper's FFT cluster: 2^15 points = 256-pt (FFT-A) x 128-pt (FFT-B)
    assert factor_m(1 << 15) == (256, 128)


@pytest.mark.parametrize("N", [512, 2048])
def test_fft_negacyclic_convolution_property(N):
    """Pointwise product in kernel transform domain == negacyclic conv."""
    rng = np.random.default_rng(N + 7)
    a = rng.integers(-64, 64, N)
    b = rng.integers(-64, 64, N)
    sa = ops.negacyclic_fft(jnp.asarray(a[None], dtype=jnp.float32))
    sb = ops.negacyclic_fft(jnp.asarray(b[None], dtype=jnp.float32))
    # complex pointwise product on stacked planes
    pr = sa[:, 0] * sb[:, 0] - sa[:, 1] * sb[:, 1]
    pi = sa[:, 0] * sb[:, 1] + sa[:, 1] * sb[:, 0]
    got = np.asarray(ops.negacyclic_ifft(jnp.stack([pr, pi], axis=1)))[0]
    # exact integer oracle
    want = np.zeros(N, dtype=np.int64)
    for i in range(N):
        k = (i + np.arange(N)) % (2 * N)
        np.add.at(want, k % N, np.where(k < N, a[i] * b, -(a[i] * b)))
    np.testing.assert_allclose(got, want, atol=np.maximum(1.0, np.abs(want).max() * 3e-5))


# --- BRU external-product MAC -------------------------------------------------

@pytest.mark.parametrize("B,J,K,F", [(1, 2, 2, 256), (12, 4, 2, 1024),
                                     (12, 6, 3, 2048), (48, 4, 2, 16384)])
def test_bru_mac_matches_ref(B, J, K, F):
    rng = np.random.default_rng(B * F)
    dig = jnp.asarray(rng.standard_normal((B, 2, J, F)) * 100, dtype=jnp.float32)
    bsk = jnp.asarray(rng.standard_normal((2, J, K, F)), dtype=jnp.float32)
    got = np.asarray(ops.bru_mac(dig, bsk))
    want = np.asarray(ref.external_product_mac_ref(dig, bsk))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("block_f", [128, 512, 2048])
def test_bru_mac_block_sweep(block_f):
    rng = np.random.default_rng(block_f)
    dig = jnp.asarray(rng.standard_normal((4, 2, 4, 2048)), dtype=jnp.float32)
    bsk = jnp.asarray(rng.standard_normal((2, 4, 2, 2048)), dtype=jnp.float32)
    got = np.asarray(ops.bru_mac(dig, bsk, block_f=block_f))
    want = np.asarray(ref.external_product_mac_ref(dig, bsk))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


# --- LPU key-switch MAC (uint32-limb 64-bit arithmetic) -----------------------

@pytest.mark.parametrize("B,S,T", [(1, 128, 65), (4, 1024, 513), (2, 4096, 257)])
def test_keyswitch_mac_exact(B, S, T):
    rng = np.random.default_rng(S + T)
    digits = jnp.asarray(
        rng.integers(-(1 << 15), 1 << 15, (B, S)), dtype=jnp.int32)
    ksk = jnp.asarray(rng.integers(0, 1 << 64, (S, T), dtype=np.uint64))
    got = np.asarray(ops.lpu_keyswitch_mac(digits, ksk))
    want = np.asarray(ref.keyswitch_mac_ref(digits, ksk))
    np.testing.assert_array_equal(got, want)  # EXACT mod 2^64


def test_keyswitch_mac_extreme_digits():
    """Full int32 digit range (negative, maximal) stays exact."""
    digits = jnp.asarray(
        [[-(1 << 31), (1 << 31) - 1, -1, 1, 0, 7, -7, 12345]], dtype=jnp.int32)
    rng = np.random.default_rng(0)
    ksk = jnp.asarray(rng.integers(0, 1 << 64, (8, 33), dtype=np.uint64))
    got = np.asarray(ops.lpu_keyswitch_mac(digits, ksk, block_s=8))
    want = np.asarray(ref.keyswitch_mac_ref(digits, ksk))
    np.testing.assert_array_equal(got, want)


def test_keyswitch_mac_grid_accumulation():
    """Multi-block S accumulation (sequential grid) is exact."""
    rng = np.random.default_rng(3)
    digits = jnp.asarray(rng.integers(-(1 << 12), 1 << 12, (3, 2048)), dtype=jnp.int32)
    ksk = jnp.asarray(rng.integers(0, 1 << 64, (2048, 129), dtype=np.uint64))
    for bs in (256, 512, 2048):
        got = np.asarray(ops.lpu_keyswitch_mac(digits, ksk, block_s=bs))
        want = np.asarray(ref.keyswitch_mac_ref(digits, ksk))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("S,block_s", [(100, 64), (2560, 1024), (33, 32)])
def test_keyswitch_mac_unaligned_block_padding(S, block_s):
    """S not a multiple of the block size zero-pads exactly (the fused
    engine hits this whenever big_n*ks_level is not block-aligned)."""
    rng = np.random.default_rng(S)
    digits = jnp.asarray(rng.integers(-(1 << 12), 1 << 12, (2, S)), dtype=jnp.int32)
    ksk = jnp.asarray(rng.integers(0, 1 << 64, (S, 65), dtype=np.uint64))
    got = np.asarray(ops.lpu_keyswitch_mac(digits, ksk, block_s=block_s))
    want = np.asarray(ref.keyswitch_mac_ref(digits, ksk))
    np.testing.assert_array_equal(got, want)


# --- fused engine room (repro.kernels.fused_pbs) -----------------------------
#
# The differential contract of the tentpole: every fused entry point
# graded against the reference engine path on real key material, the
# keyswitch stage bit-for-bit.

def _encrypt_batch(ctx, B):
    key = jax.random.PRNGKey(97)
    msgs = np.arange(B) % ctx.params.plaintext_modulus
    cts = jnp.stack([ctx.encrypt(jax.random.fold_in(key, i), int(m))
                     for i, m in enumerate(msgs)])
    return cts, msgs


def test_keyswitch_fused_bit_identical(ctx_2bit, pallas_engine_2bit):
    """Fused uint32-limb keyswitch == lwe.keyswitch, bit-for-bit."""
    from repro.core import lwe
    p = ctx_2bit.params
    cts, _ = _encrypt_batch(ctx_2bit, 5)
    want = lwe.keyswitch(cts, ctx_2bit.ksk, p.ks_base_log, p.ks_level)
    got = pallas_engine_2bit.fused_pack.keyswitch(cts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B", [1, 4])
def test_blind_rotate_fused_matches_reference(ctx_2bit, pallas_engine_2bit, B):
    """Fused blind rotation (FFT + BRU MAC kernels, f64 planes) extracts
    to the same decrypted digits as the complex128 reference.

    NB: raw GLWE coefficients are NOT compared — after the first CMux
    round, ~2^29 transform-rounding differences can flip a gadget-
    decompose digit at a rounding boundary, swinging individual mask
    coefficients by a whole GGSW row while the PHASE (what decrypts)
    moves only ~2^40 << delta.  The decrypt-level contract is the
    meaningful one for chained CMux."""
    from repro.core import batch as batch_mod, glwe, lwe
    p = ctx_2bit.params
    cts, msgs = _encrypt_batch(ctx_2bit, B)
    small = lwe.keyswitch(cts, ctx_2bit.ksk, p.ks_base_log, p.ks_level)
    ms = lwe.mod_switch(small, p.log2_N + 1)
    table = jnp.arange(p.plaintext_modulus, dtype=jnp.uint64)
    poly = glwe.make_lut_poly(table, p)
    luts = glwe.trivial(jnp.broadcast_to(poly, (B, p.N)), p.k)
    want = glwe.sample_extract(
        batch_mod.blind_rotate_batch(luts, ms, ctx_2bit.bsk_f, p))
    got = glwe.sample_extract(
        pallas_engine_2bit.fused_pack.blind_rotate(luts, ms))
    dec_ref = [int(ctx_2bit.decrypt(v)) for v in want]
    dec_pal = [int(ctx_2bit.decrypt(v)) for v in got]
    assert dec_pal == dec_ref == [int(m) for m in msgs]


@pytest.mark.parametrize("B", [1, 5, 12])
def test_pbs_batch_fused_decrypt_identical(ctx_2bit, engine_2bit,
                                           pallas_engine_2bit, B):
    """End-to-end fused lut_batch decrypts identically to reference."""
    from repro.core import glwe
    p = ctx_2bit.params
    cts, msgs = _encrypt_batch(ctx_2bit, B)
    table = jnp.asarray([(3 * v + 1) % p.plaintext_modulus
                         for v in range(p.plaintext_modulus)], dtype=jnp.uint64)
    polys = jnp.broadcast_to(glwe.make_lut_poly(table, p), (B, p.N))
    out_ref = engine_2bit.lut_batch(cts, polys)
    out_pal = pallas_engine_2bit.lut_batch(cts, polys)
    dec_ref = [int(ctx_2bit.decrypt(v)) for v in out_ref]
    dec_pal = [int(ctx_2bit.decrypt(v)) for v in out_pal]
    assert dec_pal == dec_ref == [(3 * int(m) + 1) % p.plaintext_modulus
                                  for m in msgs]


@pytest.mark.slow
def test_pbs_batch_fused_decrypt_identical_4bit(ctx_4bit, engine_4bit,
                                                pallas_engine_4bit):
    """Same differential at 4-bit params (N=2048): the noise margin is
    tighter, so this catches precision regressions the 2-bit set hides."""
    from repro.core import glwe
    p = ctx_4bit.params
    cts, msgs = _encrypt_batch(ctx_4bit, 6)
    table = jnp.asarray([(v * v) % p.plaintext_modulus
                         for v in range(p.plaintext_modulus)], dtype=jnp.uint64)
    polys = jnp.broadcast_to(glwe.make_lut_poly(table, p), (6, p.N))
    dec_ref = [int(ctx_4bit.decrypt(v))
               for v in engine_4bit.lut_batch(cts, polys)]
    dec_pal = [int(ctx_4bit.decrypt(v))
               for v in pallas_engine_4bit.lut_batch(cts, polys)]
    assert dec_pal == dec_ref == [(int(m) ** 2) % p.plaintext_modulus
                                  for m in msgs]


def test_fused_pack_resident_across_rounds(ctx_2bit, pallas_engine_2bit):
    """The key-reuse contract: ONE pack (same device arrays) services
    multiple chained PBS rounds, and round i+1 consumes round i's output
    correctly (the BSK-resident multi-round path)."""
    from repro.core import glwe
    eng = pallas_engine_2bit
    p = ctx_2bit.params
    pack0 = eng.fused_pack
    cts, msgs = _encrypt_batch(ctx_2bit, 4)
    table = jnp.asarray([(v + 1) % p.plaintext_modulus
                         for v in range(p.plaintext_modulus)], dtype=jnp.uint64)
    polys = jnp.broadcast_to(glwe.make_lut_poly(table, p), (4, p.N))
    out = cts
    for round_i in range(3):
        out = eng.lut_batch(out, polys)
        assert eng.fused_pack is pack0          # no rebuild between rounds
        assert eng.fused_pack.bsk_planes is pack0.bsk_planes
    dec = [int(ctx_2bit.decrypt(v)) for v in out]
    assert dec == [(int(m) + 3) % p.plaintext_modulus for m in msgs]


def test_fused_pack_bytes_within_roofline_bound(pallas_engine_2bit):
    """Bandwidth gate: the pack's streamed bytes per fused round must sit
    within the analytic `launch.roofline.pbs_round_model` bound, and key
    bytes must equal the reference engine's ledger quantity exactly."""
    from repro.launch.roofline import pbs_round_model
    eng = pallas_engine_2bit
    pack = eng.fused_pack
    for B in (1, 12, 48):
        model = pbs_round_model(eng.params, B)
        assert pack.bytes_streamed_per_round(B) <= model.fused_bytes
        # key reuse only pays off past B=1 (at B=1 the two are equal)
        assert model.fused_bytes <= model.unfused_bytes
        if B > 1:
            assert model.fused_bytes < model.unfused_bytes
    bsk_b, ksk_b = pack.resident_key_bytes
    assert (bsk_b, ksk_b) == eng.key_bytes


def test_engine_kernel_backend_validation(ctx_2bit):
    """Bad backend strings and mesh+pallas are rejected at build time."""
    from repro.core.engine import TaurusEngine
    with pytest.raises(ValueError, match="kernel_backend"):
        TaurusEngine.from_context(ctx_2bit, kernel_backend="cuda")


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False)])
def test_interpret_mode_follows_platform(monkeypatch, platform, interpret):
    from repro.kernels import interpret_mode
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert interpret_mode() is interpret


def test_pallas_engine_refused_on_tpu(ctx_2bit, monkeypatch):
    """On a TPU the pallas room raises at construction, naming why;
    the reference room still builds there."""
    from repro.core.engine import ConfigError, TaurusEngine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ConfigError, match="Mosaic has no float64"):
        TaurusEngine.from_context(ctx_2bit, kernel_backend="pallas")
    assert TaurusEngine.from_context(ctx_2bit).kernel_backend == "reference"
