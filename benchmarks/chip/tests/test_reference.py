"""The plain reference and the benchmark's own client crypto."""
import jax
import numpy as np
import pytest

import client
from programs import radix
from repro.core.params import TEST_PARAMS


@pytest.mark.parametrize("op,args,bits,want", [
    ("add", [65535, 1], 16, 0),
    ("add", [1234, 567], 16, 1801),
    ("mul", [12345, 54321], 16, 28393),
    ("mul", [12345, 54321], 24, 16281321),
    ("relu", [32767], 16, 32767),
    ("relu", [32768], 16, 0),
    ("relu", [2**23 - 1], 24, 2**23 - 1),
    ("relu", [2**24 - 1], 24, 0),
])
def test_reference_by_hand(op, args, bits, want):
    assert radix.reference(op, args, bits) == want


def test_digits_round_trip():
    d = radix.digits(0xBEEF, 16, 2)
    assert d.tolist() == [3, 3, 2, 3, 2, 3, 3, 2]        # little-endian
    assert radix.from_digits(d, 2, 16) == 0xBEEF
    assert radix.from_digits(radix.digits(0xABCDEF, 24, 3), 3, 24) == \
        0xABCDEF


def test_client_crypto_agrees_with_the_program():
    """The benchmark's encryption decrypts under the program's decryption,
    and the program's encryption under the benchmark's."""
    from repro.api import IntSpec, Session
    p = TEST_PARAMS
    ctx = client.make_context(11, p)
    sess = Session(ctx, backend="eager")
    prog = sess.trace(lambda a: a, IntSpec(8, 1))
    digits = np.stack([radix.digits(v, 8, 1) for v in (0, 77, 255)])
    enc = client.encryptor(p)(jax.random.key(0), digits, ctx.big_sk)
    got = [sess.decrypt_outputs(prog, [e])[0] for e in enc]
    assert got == [0, 77, 255]
    theirs = sess.encrypt_inputs(jax.random.key(1), [201], prog)[0]
    ph = client.phases(np.asarray(theirs), np.asarray(ctx.big_sk))
    assert radix.from_digits(client.decode(ph, p), 1, 8) == 201
    share = client.noise_share(ph, radix.digits(201, 8, 1), p)
    assert share.max() < 1e-6
    wrong = client.noise_share(ph, radix.digits(200, 8, 1), p)
    assert wrong.max() >= 1.0
