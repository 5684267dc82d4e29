"""Operations and bytes the TFHE algorithm needs, from the parameters alone.

This is the yardstick for the roofline and peak shares.  It counts what
the algorithm asks for, not what an engine room happens to execute, so a
later change to the kernels cannot move it:

  keyswitch (k*N -> n):  every input coefficient is split into ks_level
      digits, each of which scales one (n+1)-word key row and adds it in:
      2 * k*N * ks_level * (n+1) operations.
  mod switch:  n+1 operations.
  blind rotation:  n CMux steps.  Each step
      - rotates the accumulator and subtracts it:  (k+1)*N,
      - transforms (k+1)*pbs_level gadget digit polynomials forward,
      - multiplies them into the (k+1) key rows in the transform domain
        and sums:  (k+1) * (k+1)*pbs_level complex multiply-adds of N/2
        points, 8 real operations each,
      - transforms the (k+1) results back,
      - adds them into the accumulator:  (k+1)*N.
    A negacyclic product of degree N is one complex transform of N/2
    points (the "double-real" fold of the paper's section IV-C), counted
    as 5 * M * log2(M) for the transform plus 6 * M for the twist.
  sample extract:  a permutation, no arithmetic.

Bytes: the evaluation keys are read once per round, whatever the number
of rows, and each row reads its big-key ciphertext and its LUT polynomial
and writes one big-key ciphertext.  Key bytes are those of
`repro.launch.roofline.pbs_round_model`: the bootstrapping key as
(n, k+1, pbs_level, k+1, N/2) complex values of 16 bytes, the keyswitch
key as (k*N, ks_level, n+1) words of 8 bytes.
"""
from __future__ import annotations

import dataclasses
import math


def transform_ops(N: int) -> int:
    """Real operations of one forward or inverse negacyclic transform of a
    degree-N polynomial: an M = N/2 point complex FFT plus the twist."""
    m = N // 2
    return 5 * m * int(math.log2(m)) + 6 * m


def cmux_ops(p) -> int:
    """Real operations of one CMux step of the blind rotation."""
    kp1, m = p.k + 1, p.N // 2
    forward = kp1 * p.pbs_level * transform_ops(p.N)
    mac = 8 * kp1 * kp1 * p.pbs_level * m
    inverse = kp1 * transform_ops(p.N)
    rotate_add = 2 * kp1 * p.N
    return forward + mac + inverse + rotate_add


def keyswitch_ops(p) -> int:
    return 2 * p.k * p.N * p.ks_level * (p.n + 1)


def blind_rotate_ops(p) -> int:
    """Mod switch plus the n CMux steps (sample extract is free)."""
    return (p.n + 1) + p.n * cmux_ops(p)


def pbs_ops(p) -> int:
    """One full programmable bootstrap: keyswitch, then blind rotation."""
    return keyswitch_ops(p) + blind_rotate_ops(p)


def bsk_bytes(p) -> int:
    return p.n * (p.k + 1) * p.pbs_level * (p.k + 1) * (p.N // 2) * 16


def ksk_bytes(p) -> int:
    return p.k * p.N * p.ks_level * (p.n + 1) * 8


def row_bytes(p) -> int:
    """Per row: big-key ciphertext in and out, LUT polynomial in."""
    ct = (p.k * p.N + 1) * 8
    return 2 * ct + p.N * 8


@dataclasses.dataclass(frozen=True)
class RoundWork:
    """The work of one fused round: `rows` blind rotations, of which
    `keyswitched` needed their own keyswitch (rows sharing a ciphertext
    share one)."""
    rows: int
    keyswitched: int

    def ops(self, p) -> int:
        return self.rows * blind_rotate_ops(p) + \
            self.keyswitched * keyswitch_ops(p)

    def bytes(self, p) -> int:
        return bsk_bytes(p) + ksk_bytes(p) + self.rows * row_bytes(p)

    def least_seconds(self, p, peak_ops: float, peak_bytes: float) -> float:
        """The least time the chip could take: the larger of operations
        over peak operation rate and bytes over peak bandwidth."""
        return max(self.ops(p) / peak_ops, self.bytes(p) / peak_bytes)
