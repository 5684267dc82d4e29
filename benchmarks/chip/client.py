"""The client's side of encrypted serving, written apart from the program.

The benchmark plays the client: it draws the secret keys from the seed,
encrypts every request's digits, and decrypts the answers.  Only the
evaluation keys (bootstrapping and keyswitch keys) are built with the
program's own key generation, from these secret keys, because the server
needs them in its layout.  Decryption and decoding here use numpy and the
formulas of the scheme, never the program's code, so a fault in the
program cannot hide in the check.

Encoding (TFHE with one padding bit): a digit m of a `width`-bit window is
the torus value m * delta, delta = 2^(64 - width - 1); a big-key LWE
ciphertext (a, b) of length k*N + 1 has phase b - <a, s> mod 2^64.
"""
from __future__ import annotations

import numpy as np

from loadgen import seeded_rng

MASK64 = (1 << 64) - 1


def secret_keys(seed: int, p):
    """(lwe_sk (n,), glwe_sk (k, N)) uniform binary uint64 keys."""
    rng = np.random.default_rng(seeded_rng("secret keys", seed)
                                .getrandbits(63))
    lwe_sk = rng.integers(0, 2, p.n, dtype=np.uint64)
    glwe_sk = rng.integers(0, 2, (p.k, p.N), dtype=np.uint64)
    return lwe_sk, glwe_sk


def delta(p) -> int:
    return 1 << (64 - p.width - p.padding_bits)


def make_context(seed: int, p):
    """A `TFHEContext` whose secret keys are the benchmark's and whose
    evaluation keys come from one jitted call of the program's key
    generation on the default device."""
    import jax
    import jax.numpy as jnp
    from repro.core import ggsw, glwe, lwe
    from repro.core.pbs import TFHEContext

    lwe_sk, glwe_sk = secret_keys(seed, p)

    @jax.jit
    def eval_keys(key, lwe_sk, glwe_sk):
        kb, kk = jax.random.split(key)
        bsk_f = ggsw.bsk_to_fourier(ggsw.bsk_gen(kb, lwe_sk, glwe_sk, p))
        ksk = lwe.ksk_gen(kk, glwe.flatten_key(glwe_sk), lwe_sk,
                          p.ks_base_log, p.ks_level, p.lwe_std)
        return bsk_f, ksk

    key = jax.random.key(seeded_rng("eval keys", seed).getrandbits(31))
    lwe_sk_d, glwe_sk_d = jnp.asarray(lwe_sk), jnp.asarray(glwe_sk)
    bsk_f, ksk = eval_keys(key, lwe_sk_d, glwe_sk_d)
    return TFHEContext(p, lwe_sk_d, glwe_sk_d, glwe_sk_d.reshape(-1),
                       bsk_f, ksk)


def encryptor(p):
    """A jitted (key, digits uint64 (R, D), big_sk) -> (R, D, k*N+1)
    encryption of every digit under the big key, noise std glwe_std."""
    import jax
    import jax.numpy as jnp

    d = np.uint64(delta(p))
    scale = p.glwe_std * 2.0 ** 64

    @jax.jit
    def encrypt(key, digits, big_sk):
        ka, ke = jax.random.split(key)
        shape = digits.shape
        a = jax.random.bits(ka, shape + (big_sk.shape[0],), dtype=jnp.uint64)
        e = jnp.round(jax.random.normal(ke, shape, dtype=jnp.float64) * scale)
        b = ((a * big_sk).sum(axis=-1, dtype=jnp.uint64)
             + digits.astype(jnp.uint64) * d
             + e.astype(jnp.int64).astype(jnp.uint64))
        return jnp.concatenate([a, b[..., None]], axis=-1)

    return encrypt


def phases(cts: np.ndarray, big_sk: np.ndarray) -> np.ndarray:
    """Phase b - <a, s> mod 2^64 of (..., k*N+1) uint64 ciphertexts."""
    cts = np.asarray(cts, dtype=np.uint64)
    s = np.asarray(big_sk, dtype=np.uint64)
    return cts[..., -1] - (cts[..., :-1] * s).sum(axis=-1, dtype=np.uint64)


def decode(ph: np.ndarray, p) -> np.ndarray:
    """Nearest digit of each phase, in the 2^width window."""
    d = delta(p)
    return ((ph + np.uint64(d >> 1)) // np.uint64(d)) % np.uint64(
        1 << p.width)


def noise_share(ph: np.ndarray, expected_digits: np.ndarray, p) -> np.ndarray:
    """|phase - expected * delta| over the decrypt margin delta / 2, per
    digit: below 1 the digit decrypts to what was expected."""
    d = delta(p)
    err = (ph - expected_digits.astype(np.uint64) * np.uint64(d)).astype(
        np.int64)
    return np.abs(err.astype(np.float64)) / (d / 2)
