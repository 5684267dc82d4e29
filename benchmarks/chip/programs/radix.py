"""Program family `radix`: encrypted W-bit integers in radix form.

A W-bit integer is a little-endian vector of D = W / msg_bits digits, each
one LWE ciphertext of a `width`-bit window (msg_bits of message, the rest
carry), the layout of TFHE-rs's radix integers.  The served programs are
traced once through the program's front door (`Session.trace`); the
reference is plain Python integer arithmetic.
"""
from __future__ import annotations

import numpy as np

ARITY = {"add": 2, "mul": 2, "relu": 1}


def reference(op: str, args: list, bits: int) -> int:
    """The plain answer: (a + b) mod 2^W, (a * b) mod 2^W, or ReLU of the
    two's-complement reading of a."""
    mod = 1 << bits
    if op == "add":
        return (args[0] + args[1]) % mod
    if op == "mul":
        return (args[0] * args[1]) % mod
    if op == "relu":
        return args[0] if args[0] < mod // 2 else 0
    raise ValueError(f"unknown radix operation {op!r}")


def digits(value: int, bits: int, msg_bits: int) -> np.ndarray:
    """Little-endian digits of value mod 2^W."""
    value %= 1 << bits
    mask = (1 << msg_bits) - 1
    return np.array([(value >> (i * msg_bits)) & mask
                     for i in range(bits // msg_bits)], dtype=np.uint64)


def from_digits(ds, msg_bits: int, bits: int) -> int:
    """Weighted sum of decoded digits mod 2^W (tolerates carries left in a
    digit, which the noise check then reports)."""
    return sum(int(d) << (i * msg_bits) for i, d in enumerate(ds)) % (
        1 << bits)


def build(sess, cfg: dict) -> dict:
    """{op: Program} for every operation of the family."""
    from repro.api import IntSpec
    bits, mb = cfg["bits"], cfg["msg_bits"]
    fns = {"add": lambda a, b: a + b, "mul": lambda a, b: a * b,
           "relu": lambda a: a.relu()}
    return {op: sess.trace(fns[op], *[IntSpec(bits, mb)] * ARITY[op])
            for op in ARITY}
