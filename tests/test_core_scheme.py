"""End-to-end correctness of the TFHE scheme: the paper's substrate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decompose as dec, fft, ggsw, glwe, lwe, torus
from repro.core.params import TEST_PARAMS, TEST_PARAMS_4BIT, TEST_PARAMS_K2
from repro.core.pbs import TFHEContext, pbs

U64 = jnp.uint64


def test_decompose_recompose_close():
    key = jax.random.key(0)
    v = jax.random.bits(key, (1024,), dtype=U64)
    for bl, lv in [(4, 5), (8, 3), (12, 2), (23, 1)]:
        d = dec.decompose(v, bl, lv)
        assert int(jnp.max(jnp.abs(d))) <= (1 << bl) // 2
        r = dec.recompose(d, bl, lv)
        err = torus.to_signed(r - v)
        bound = 1 << (64 - bl * lv)  # rounding cut
        assert int(jnp.max(jnp.abs(err))) <= bound


def test_lwe_encrypt_decrypt():
    p = TEST_PARAMS
    key = jax.random.key(1)
    sk = lwe.keygen(key, p.n)
    msgs = jnp.arange(p.plaintext_modulus, dtype=U64)
    ct = lwe.encrypt(jax.random.key(2), sk, torus.encode(msgs, p.delta), p.lwe_std)
    ph = lwe.decrypt_phase(sk, ct)
    out = torus.decode(ph, p.delta, p.plaintext_modulus)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(msgs))


def test_lwe_linear_ops():
    p = TEST_PARAMS_4BIT
    sk = lwe.keygen(jax.random.key(3), p.n)
    enc = lambda k, m: lwe.encrypt(
        jax.random.key(k), sk, torus.encode(jnp.asarray(m, dtype=U64), p.delta), p.lwe_std
    )
    c3, c5 = enc(10, 3), enc(11, 5)
    dec_ = lambda ct: int(torus.decode(
        lwe.decrypt_phase(sk, ct), p.delta, p.plaintext_modulus))
    assert dec_(lwe.add(c3, c5)) == 8
    assert dec_(lwe.sub(c5, c3)) == 2
    assert dec_(lwe.scalar_mul(c3, 2)) == 6
    assert dec_(lwe.add_plain(c3, torus.encode(jnp.asarray(4, dtype=U64), p.delta))) == 7


def test_glwe_encrypt_decrypt():
    p = TEST_PARAMS
    sk = glwe.keygen(jax.random.key(4), p.k, p.N)
    msg = torus.encode(
        jax.random.randint(jax.random.key(5), (p.N,), 0, p.plaintext_modulus, dtype=jnp.int64).astype(U64),
        p.delta,
    )
    ct = glwe.encrypt(jax.random.key(6), sk, msg, p.glwe_std)
    ph = glwe.decrypt_phase(sk, ct)
    out = torus.decode(ph, p.delta, p.plaintext_modulus)
    want = torus.decode(msg, p.delta, p.plaintext_modulus)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_glwe_rotate_matches_monomial_mul():
    N = 64
    rng = np.random.default_rng(7)
    poly_np = rng.integers(0, 1 << 64, N, dtype=np.uint64)
    poly = jnp.asarray(poly_np)
    for r in [0, 1, 5, N - 1, N, N + 3, 2 * N - 1]:
        # exact integer oracle: X^r * poly mod (X^N+1, 2^64)
        want = np.zeros(N, dtype=np.uint64)
        with np.errstate(over="ignore"):  # intended mod-2^64 wraparound
            for i in range(N):
                e = (i + r) % (2 * N)
                if e < N:
                    want[e] += poly_np[i]
                else:
                    want[e - N] -= poly_np[i]
        got = glwe.rotate(poly, jnp.asarray(r), N)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_sample_extract():
    p = TEST_PARAMS
    gsk = glwe.keygen(jax.random.key(8), p.k, p.N)
    msg = torus.encode(
        jax.random.randint(jax.random.key(9), (p.N,), 0, p.plaintext_modulus, dtype=jnp.int64).astype(U64),
        p.delta,
    )
    ct = glwe.encrypt(jax.random.key(10), gsk, msg, p.glwe_std)
    ext = glwe.sample_extract(ct)
    big = glwe.flatten_key(gsk)
    ph = lwe.decrypt_phase(big, ext)
    got = int(torus.decode(ph, p.delta, p.plaintext_modulus))
    want = int(torus.decode(msg[0], p.delta, p.plaintext_modulus))
    assert got == want


def test_external_product_selects():
    """ext_prod(GGSW(s), GLWE(M)) decrypts to s*M for s in {0,1}."""
    p = TEST_PARAMS
    gsk = glwe.keygen(jax.random.key(11), p.k, p.N)
    msg = torus.encode(
        jax.random.randint(jax.random.key(12), (p.N,), 0, p.plaintext_modulus, dtype=jnp.int64).astype(U64),
        p.delta,
    )
    ct = glwe.encrypt(jax.random.key(13), gsk, msg, p.glwe_std)
    for bit in (0, 1):
        gg = ggsw.encrypt_bit(
            jax.random.key(14 + bit), gsk, jnp.asarray(bit, dtype=U64),
            p.pbs_base_log, p.pbs_level, p.glwe_std,
        )
        out = ggsw.external_product_fourier(
            ggsw.ggsw_to_fourier(gg), ct, p.pbs_base_log, p.pbs_level
        )
        ph = glwe.decrypt_phase(gsk, out)
        got = torus.decode(ph, p.delta, p.plaintext_modulus)
        want = (bit * np.asarray(torus.decode(msg, p.delta, p.plaintext_modulus))) % p.plaintext_modulus
        np.testing.assert_array_equal(np.asarray(got), want)


def test_keyswitch():
    p = TEST_PARAMS
    k1, k2, k3, k4 = jax.random.split(jax.random.key(20), 4)
    sk_big = lwe.keygen(k1, p.big_n)
    sk_small = lwe.keygen(k2, p.n)
    ksk = lwe.ksk_gen(k3, sk_big, sk_small, p.ks_base_log, p.ks_level, p.lwe_std)
    msgs = jnp.arange(p.plaintext_modulus, dtype=U64)
    ct = lwe.encrypt(k4, sk_big, torus.encode(msgs, p.delta), p.lwe_std)
    out = lwe.keyswitch(ct, ksk, p.ks_base_log, p.ks_level)
    got = torus.decode(lwe.decrypt_phase(sk_small, out), p.delta, p.plaintext_modulus)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(msgs))


def _pbs_identity(ctx):
    mod = ctx.params.plaintext_modulus
    table = list(range(mod))
    for m in range(mod):
        ct = ctx.encrypt(jax.random.key(100 + m), m)
        out = ctx.lut(ct, table)
        assert int(ctx.decrypt(out)) == m, f"PBS identity failed at m={m}"


def test_pbs_identity_all_messages(ctx_2bit):
    _pbs_identity(ctx_2bit)


def test_pbs_identity_all_messages_k2():
    # k=2 stays locally created: tiny params, no session fixture for it
    _pbs_identity(TFHEContext.create(jax.random.key(30), TEST_PARAMS_K2))


def test_pbs_nontrivial_lut_and_noise_refresh(ctx_4bit):
    ctx = ctx_4bit
    params = ctx.params
    mod = params.plaintext_modulus
    relu_shift = [max(0, m - 8) for m in range(mod)]  # ReLU(m-8) as in Fig. 2
    for m in [0, 3, 7, 8, 9, 15]:
        ct = ctx.encrypt(jax.random.key(200 + m), m)
        out = ctx.lut(ct, relu_shift)
        assert int(ctx.decrypt(out)) == max(0, m - 8)
        # bootstrapping refreshes noise: output noise well under half a slot
        n = abs(float(ctx.decrypt_noise(out, max(0, m - 8))))
        assert n < 1.0 / (2 ** (params.width + 2))


def test_pbs_chain_depth(ctx_2bit):
    """Repeated PBS keeps working: noise does not accumulate across ops."""
    ctx = ctx_2bit
    params = ctx.params
    inc = [(m + 1) % params.plaintext_modulus for m in range(params.plaintext_modulus)]
    ct = ctx.encrypt(jax.random.key(33), 0)
    for i in range(4):
        ct = ctx.lut(ct, inc)
        assert int(ctx.decrypt(ct)) == (i + 1) % params.plaintext_modulus


def test_decompose_recompose_exact_identity():
    """When the gadget covers the full 64-bit word (base_log*level == 64),
    recompose o decompose is the IDENTITY, not just an approximation."""
    v = jax.random.bits(jax.random.key(77), (512,), dtype=U64)
    for bl, lv in [(4, 16), (8, 8), (16, 4), (32, 2)]:
        d = dec.decompose(v, bl, lv)
        r = dec.recompose(d, bl, lv)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(v))


def test_rotate_identity_and_extremes():
    """glwe.rotate edge cases: r=0 is the identity; r=2N-1 multiplies by
    X^{-1} (coefficients shift down, the wrapped one negated); r=N is
    global negation.  Checked on a full (k+1, N) GLWE layout."""
    N = 32
    rng = np.random.default_rng(3)
    ct = jnp.asarray(rng.integers(0, 1 << 64, (2, N), dtype=np.uint64))
    np.testing.assert_array_equal(
        np.asarray(glwe.rotate(ct, jnp.asarray(0), N)), np.asarray(ct))
    got = np.asarray(glwe.rotate(ct, jnp.asarray(2 * N - 1), N))
    want = np.empty_like(np.asarray(ct))
    want[:, : N - 1] = np.asarray(ct)[:, 1:]            # c_{j+1} -> slot j
    want[:, N - 1] = (-np.asarray(ct)[:, :1].astype(np.int64)
                      ).astype(np.uint64).ravel()       # -c_0 wraps to top
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(glwe.rotate(ct, jnp.asarray(N), N)),
        (-np.asarray(ct).astype(np.int64)).astype(np.uint64))
