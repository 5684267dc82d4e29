"""The TPU's engine room, run on the CPU: the int8 block layout and the
integer external product (`repro.core.blockmul`).

The product must equal an exact schoolbook product mod 2^64 bit for bit,
and a PBS through it must decrypt as the float64 room does, its phases
within the float64 room's error budget (`tests/test_core_fft.py`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batch, blockmul, ggsw, glwe, lwe, pbs, torus
from repro.core.params import (PAPER_PARAMS, TEST_PARAMS, TEST_PARAMS_4BIT,
                               TEST_PARAMS_K2)

U64 = jnp.uint64
# torus values whose eight balanced int8 limbs are all -128 / all 127
ALL_NEG = sum(-128 << (8 * c) for c in range(8)) % (1 << 64)
ALL_POS = sum(127 << (8 * c) for c in range(8))
F64_BUDGET = 1 << 28     # per product, tests/test_core_fft.py


def schoolbook(dig: np.ndarray, tor: np.ndarray) -> np.ndarray:
    """Negacyclic sum_m dig[m] X^m * tor, mod 2^64 (numpy uint64 wraps)."""
    N = tor.shape[-1]
    idx = np.arange(N)[:, None] - np.arange(N)[None, :]
    sign = np.where(idx < 0, np.uint64(2 ** 64 - 1), np.uint64(1))
    mat = tor[idx % N] * sign                         # +-tor[i - m]
    return (mat * dig.astype(np.int64).astype(np.uint64)[None, :]).sum(
        axis=1, dtype=np.uint64)


def reference(digits: np.ndarray, ggsw_ct: np.ndarray) -> np.ndarray:
    """(B, J, N) digits, (k+1, level, K, N) GGSW -> (B, K, N) uint64."""
    B, J, N = digits.shape
    S = ggsw_ct.reshape(J, -1, N)
    out = np.zeros((B, S.shape[1], N), np.uint64)
    for b in range(B):
        for k in range(S.shape[1]):
            for j in range(J):
                out[b, k] += schoolbook(digits[b, j], S[j, k])
    return out


def test_extreme_limbs():
    limbs = np.asarray(blockmul.balanced_limbs(
        jnp.asarray([ALL_NEG, ALL_POS], U64), 8, axis=0))
    assert (limbs[:, 0] == -128).all() and (limbs[:, 1] == 127).all()


@pytest.mark.parametrize("N,base_log,level,k", [
    (64, 23, 1, 1), (256, 12, 2, 1), (256, 12, 2, 2), (512, 14, 2, 1)])
def test_external_product_is_exact(N, base_log, level, k):
    rng = np.random.default_rng(N * base_log + k)
    K, J, B = k + 1, (k + 1) * level, 3
    g = rng.integers(0, 2 ** 64, (K, level, K, N), dtype=np.uint64)
    g[0, 0, 0, : N // 2] = ALL_NEG
    g[-1, -1, -1, N // 2:] = ALL_POS
    half = 1 << (base_log - 1)
    dig = rng.integers(-half, half + 1, (B, J, N))
    dig[0] = np.where(rng.integers(0, 2, (J, N)) == 1, half, -half)
    dig[1, :, ::2] = -half
    # row 2 against a key of all -128 limbs: the largest partial sums
    g2 = np.full_like(g, ALL_NEG)
    dig[2] = -half
    blocks = jax.jit(blockmul.ggsw_to_blocks)
    prod = jax.jit(lambda y, d: blockmul.external_product(y, d, K, base_log))
    got = np.asarray(prod(blocks(jnp.asarray(g)), jnp.asarray(dig[:2])))
    np.testing.assert_array_equal(got, reference(dig[:2], g))
    got2 = np.asarray(prod(blocks(jnp.asarray(g2)), jnp.asarray(dig[2:])))
    np.testing.assert_array_equal(got2, reference(dig[2:], g2))


@pytest.mark.parametrize("n,N,k,level,gib", [
    (742, 2048, 1, 1, 3.11),                 # msg2carry2
    (PAPER_PARAMS["cnn20"].n, 2048, 1, 1, 3.09),
    (TEST_PARAMS_4BIT.n, 2048, 1, 2, 0.81),
    (PAPER_PARAMS["cnn50"].n, 4096, 1, 1, 6.95)])
def test_block_layout_shape_and_bytes(n, N, k, level, gib):
    bsk = jax.ShapeDtypeStruct((n, k + 1, level, k + 1, N), U64)
    out = jax.eval_shape(blockmul.bsk_to_blocks, bsk)
    K, J = k + 1, (k + 1) * level
    b = blockmul.block_size(N, K)
    assert K * b % 128 == 0
    assert out.shape == (n, N * J, 8 * K * b) and out.dtype == jnp.int8
    nbytes = out.size * out.dtype.itemsize
    assert nbytes == n * N * J * 8 * K * b
    assert abs(nbytes / 1e9 - gib) < 0.01
    assert blockmul.exact(N, J)


def test_cpu_builds_f64_planes():
    bsk = jax.ShapeDtypeStruct((4, 2, 1, 2, 2048), U64)
    assert not ggsw.int8_room(2048, 2)          # not on a TPU
    planes = jax.eval_shape(ggsw.bsk_to_fourier, bsk)
    assert planes.dtype == jnp.float64 and planes.shape == (4, 2, 2, 2, 1024)
    assert ggsw.key_layout(planes) == "f64_planes"


@pytest.mark.parametrize("p", [TEST_PARAMS, TEST_PARAMS_K2],
                         ids=lambda p: p.name)
def test_pbs_through_int8_layout_matches_f64_room(p):
    """Same keys and inputs through both rooms.  One external product
    differs by no more than the f64 budget per product.  A whole PBS
    cannot be held to it: an accumulator coefficient that differs in its
    last bits can round to the other gadget digit, which moves the phase
    by up to the decomposition's rounding error, as the scheme's noise
    does.  So the PBS decrypts must be equal and the int8 room's phase
    error no larger than twice the f64 room's."""
    @jax.jit
    def keys(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        lwe_sk = lwe.keygen(k1, p.n)
        glwe_sk = glwe.keygen(k2, p.k, p.N)
        big_sk = glwe.flatten_key(glwe_sk)
        bsk = ggsw.bsk_gen(k3, lwe_sk, glwe_sk, p)
        ksk = lwe.ksk_gen(k4, big_sk, lwe_sk, p.ks_base_log, p.ks_level,
                          p.lwe_std)
        return (glwe_sk, big_sk, jax.vmap(ggsw.ggsw_to_fourier)(bsk),
                blockmul.bsk_to_blocks(bsk), ksk)

    glwe_sk, big_sk, planes, blocks, ksk = keys(jax.random.key(7))
    assert ggsw.key_layout(blocks) == "int8_blocks"
    J = (p.k + 1) * p.pbs_level

    @jax.jit
    def step_gap(planes, blocks):
        ct = glwe.encrypt(jax.random.key(9), glwe_sk, jnp.zeros(p.N, U64),
                          p.glwe_std)
        ext = lambda key: ggsw.external_product_fourier(
            key, ct, p.pbs_base_log, p.pbs_level)
        return jnp.abs(torus.to_signed(ext(blocks[0]) - ext(planes[0]))).max()

    assert int(step_gap(planes, blocks)) <= J * F64_BUDGET

    mod = p.plaintext_modulus
    msgs = jnp.arange(mod, dtype=U64)
    table = (3 * jnp.arange(mod, dtype=U64) + 1) % mod
    cts = jax.jit(lwe.encrypt, static_argnums=3)(
        jax.random.key(8), big_sk, torus.encode(msgs, p.delta), p.glwe_std)
    polys = jnp.broadcast_to(glwe.make_lut_poly(table, p), (mod, p.N))
    out_f = batch.pbs_batch(cts, polys, planes, ksk, p)
    out_i = batch.pbs_batch(cts, polys, blocks, ksk, p)

    phase = lambda c: lwe.decrypt_phase(big_sk, c)
    dec = lambda c: np.asarray(torus.decode(phase(c), p.delta, mod))
    np.testing.assert_array_equal(dec(out_i), np.asarray(table))
    np.testing.assert_array_equal(dec(out_i), dec(out_f))
    err = lambda c: np.abs(np.asarray(torus.to_signed(
        phase(c) - torus.encode(table, p.delta)))).max()
    assert err(out_i) <= 2 * err(out_f)
    # the unbatched CMux path takes the same exact product
    one = pbs.pbs(cts[1], polys[1], blocks, ksk, p)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(out_i[1]))


def test_correlations_agree():
    """The TPU's convolution and the CPU's stacked-window matmul."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-128, 128, (6, 7, 128)), jnp.int8)
    kernel = jnp.asarray(rng.integers(-128, 128, (4, 128, 256)), jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(blockmul.correlate_conv(x, kernel)),
        np.asarray(blockmul.correlate_windows(x, kernel)))
