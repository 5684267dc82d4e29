"""The fused Pallas PBS engine room: kernels wired into one hot path.

This module is what `TaurusEngine(kernel_backend="pallas")` runs.  It
fuses the three Pallas kernels into the batched KS-first PBS pipeline
(paper Fig. 3, steps A-D) with the paper's key-reuse strategy made
explicit as RESIDENT operands:

    keyswitch     `kernels.keyswitch` — uint32-limb 64-bit MAC over the
                  gadget digits of the whole batch (exact mod 2^64, so
                  this stage is BIT-IDENTICAL to `repro.core.lwe`).
    blind rotate  per scan step: decompose the CMux difference, forward
                  `kernels.fourstep_fft`, one `kernels.external_product`
                  MAC against the resident BSK slice, inverse FFT back
                  to torus coefficients.
    extract       `repro.core.glwe.sample_extract` (LPU layout work).

`FusedPbsPack` is the residency contract: the Fourier BSK (already in
the kernels' stacked re/im plane layout) is cast to the plane dtype
ONCE per key, and the KSK is limb-split into (hi, lo) uint32 planes
ONCE — every subsequent
`lut_batch` round of every fused wave consumes the same device arrays.
That is the paper's §III-B round-robin key reuse: arithmetic intensity
on the key stream scales with the fused batch size because the operand
never has to be re-derived (and on hardware, re-fetched) per round.

Precision: the transform-domain planes default to f64, which interpret
mode (CPU) executes natively and the 64-bit torus needs — an f32-only
transform would put ~2^60+ of error into the accumulator, voiding
decryption.  Mosaic has no f64, so `TaurusEngine` refuses this engine
room on a TPU; the kernels themselves compile there at f32 (pinned by
`tests/test_tpu_compile.py`), the starting point for the paper's
split fixed-point planes (Obs. 4, ROADMAP S2).  The `dtype` switch is
the seam where that lands.  The keyswitch limb kernel is uint32 end to
end and therefore exact on any hardware.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import decompose as dec, glwe, lwe, torus
from repro.core import batch as batch_mod
from repro.core.params import TFHEParams
from repro.kernels import external_product, fourstep_fft, keyswitch, ref

U64 = jnp.uint64


def ksk_to_limbs(ksk: jax.Array) -> tuple[jax.Array, jax.Array]:
    """KSK (n_from, level, n_to+1) uint64 -> (hi, lo) (S, T) uint32 limb
    planes, S = n_from*level flattened in the digit order
    `lwe.keyswitch` contracts over."""
    n_from, level, t = ksk.shape
    return ref.split_u64(ksk.reshape(n_from * level, t))


@functools.partial(jax.jit,
                   static_argnames=("params", "block_s", "interpret"))
def keyswitch_fused(big_cts: jax.Array, ksk_hi: jax.Array, ksk_lo: jax.Array,
                    params: TFHEParams, *, block_s: int = 1024,
                    interpret: bool | None = None) -> jax.Array:
    """Batched big->small key switch through the limb MAC kernel.

    (B, big_n+1) -> (B, n+1); exact mod 2^64, bit-identical to
    `lwe.keyswitch` (pinned by tests/test_kernels.py).
    """
    a, b = big_cts[..., :-1], big_cts[..., -1]
    digits = dec.decompose(a, params.ks_base_log, params.ks_level)
    digits = digits.reshape(digits.shape[0], -1).astype(jnp.int32)
    hi, lo = keyswitch.keyswitch_mac(digits, ksk_hi, ksk_lo,
                                     block_s=block_s, interpret=interpret)
    out = -ref.merge_u64(hi, lo)
    return out.at[..., -1].add(b)


def external_product_planes(bsk_i: jax.Array, glwe_cts: jax.Array,
                            params: TFHEParams, *, dtype=jnp.float64,
                            block_f: int = 2048,
                            interpret: bool | None = None) -> jax.Array:
    """One resident BSK slice (2, J, K, M) applied to a GLWE batch
    (B, K, N) — decompose, forward FFT kernel, BRU MAC kernel, inverse
    FFT kernel, back onto the torus."""
    B, K, N = glwe_cts.shape
    M = N // 2
    J = K * params.pbs_level
    digs = dec.decompose(glwe_cts, params.pbs_base_log, params.pbs_level)
    digs = jnp.moveaxis(digs, -1, -2).reshape(B, J, N)      # (B, K, level, N)
    spec = fourstep_fft.fft_forward(digs.reshape(B * J, N).astype(dtype),
                                    interpret=interpret, dtype=dtype)
    dig_planes = spec.reshape(B, J, 2, M).transpose(0, 2, 1, 3)
    out = external_product.external_product_mac(
        dig_planes, bsk_i, block_f=min(block_f, M), interpret=interpret,
        dtype=dtype)                                        # (B, 2, K, M)
    coeffs = fourstep_fft.fft_inverse(
        out.transpose(0, 2, 1, 3).reshape(B * K, 2, M),
        interpret=interpret, dtype=dtype)
    return torus.float_to_torus(coeffs.astype(jnp.float64)).reshape(B, K, N)


@functools.partial(jax.jit,
                   static_argnames=("params", "dtype", "block_f", "interpret"))
def blind_rotate_fused(lut_glwes: jax.Array, ms_cts: jax.Array,
                       bsk_planes: jax.Array, params: TFHEParams, *,
                       dtype=jnp.float64, block_f: int = 2048,
                       interpret: bool | None = None) -> jax.Array:
    """Batched blind rotation over the RESIDENT plane-layout BSK.

    lut_glwes (B, k+1, N); ms_cts (B, n+1) mod-switched to [0, 2N);
    bsk_planes (n, 2, J, K, M) — scanned once, shared by the whole
    batch (the fused wave's key-reuse MAC).
    """
    N = params.N
    a, b = ms_cts[:, :-1], ms_cts[:, -1]
    acc = batch_mod.rotate_batch(lut_glwes, (2 * N - b) % (2 * N), N)

    def step(acc, inp):
        a_i, bsk_i = inp                                    # a_i: (B,)
        rotated = batch_mod.rotate_batch(acc, a_i, N)
        acc = acc + external_product_planes(
            bsk_i, rotated - acc, params, dtype=dtype, block_f=block_f,
            interpret=interpret)
        return acc, None

    acc, _ = jax.lax.scan(step, acc, (a.T, bsk_planes))
    return acc


@functools.partial(jax.jit,
                   static_argnames=("params", "dtype", "block_f", "block_s",
                                    "interpret"))
def pbs_batch_fused(big_cts: jax.Array, lut_polys: jax.Array,
                    bsk_planes: jax.Array, ksk_hi: jax.Array,
                    ksk_lo: jax.Array, params: TFHEParams, *,
                    dtype=jnp.float64, block_f: int = 2048,
                    block_s: int = 1024,
                    interpret: bool | None = None) -> jax.Array:
    """The fused fast path for `TaurusEngine.lut_batch`:
    (B, k*N+1) + (B, N) LUT polys -> (B, k*N+1), all four PBS stages on
    the Pallas kernels with resident key operands."""
    small = keyswitch_fused(big_cts, ksk_hi, ksk_lo, params,
                            block_s=block_s, interpret=interpret)
    ms = lwe.mod_switch(small, params.log2_N + 1)
    luts = glwe.trivial(lut_polys, params.k)
    acc = blind_rotate_fused(luts, ms, bsk_planes, params, dtype=dtype,
                             block_f=block_f, interpret=interpret)
    return glwe.sample_extract(acc)


@functools.partial(jax.jit,
                   static_argnames=("params", "dtype", "block_f",
                                    "interpret"))
def pbs_small_fused(small_cts: jax.Array, lut_polys: jax.Array,
                    bsk_planes: jax.Array, params: TFHEParams, *,
                    dtype=jnp.float64, block_f: int = 2048,
                    interpret: bool | None = None) -> jax.Array:
    """`pbs_batch_fused` minus the keyswitch: (B, n+1) small-key cts +
    (B, N) LUT polys -> (B, k*N+1).  `keyswitch_fused` followed by this
    function runs exactly the stages of `pbs_batch_fused`, so the
    serving scheduler's KS-level partial dedup (key-switch unique
    ciphertexts once, blind-rotate every table) stays decrypt-identical
    on the pallas backend too."""
    ms = lwe.mod_switch(small_cts, params.log2_N + 1)
    luts = glwe.trivial(lut_polys, params.k)
    acc = blind_rotate_fused(luts, ms, bsk_planes, params, dtype=dtype,
                             block_f=block_f, interpret=interpret)
    return glwe.sample_extract(acc)


@dataclasses.dataclass
class FusedPbsPack:
    """Resident kernel operands for one evaluation-key pair.

    Built once per engine (`TaurusEngine` caches it on first pallas
    `lut_batch`) and reused by every subsequent round — the arrays here
    ARE the key-reuse residency the paper banks on, so tests assert the
    same objects service multiple rounds.
    """
    params: TFHEParams
    bsk_planes: jax.Array            # (n, 2, J, K, M) dtype planes
    ksk_hi: jax.Array                # (S, T) uint32
    ksk_lo: jax.Array                # (S, T) uint32
    dtype: object = jnp.float64
    block_f: int = 2048
    block_s: int = 1024
    interpret: bool | None = None

    @classmethod
    def build(cls, bsk_f: jax.Array, ksk: jax.Array, params: TFHEParams, *,
              dtype=jnp.float64, block_f: int = 2048, block_s: int = 1024,
              interpret: bool | None = None) -> "FusedPbsPack":
        dtype = jnp.dtype(dtype)
        hi, lo = ksk_to_limbs(ksk)
        # bsk_f is already the kernels' (n, 2, J, K, M) plane layout
        return cls(params, bsk_f.astype(dtype), hi, lo,
                   dtype=dtype, block_f=block_f, block_s=block_s,
                   interpret=interpret)

    # -- the engine entry points -------------------------------------------
    def pbs_batch(self, big_cts: jax.Array, lut_polys: jax.Array) -> jax.Array:
        return pbs_batch_fused(big_cts, lut_polys, self.bsk_planes,
                               self.ksk_hi, self.ksk_lo, self.params,
                               dtype=self.dtype, block_f=self.block_f,
                               block_s=self.block_s, interpret=self.interpret)

    def keyswitch(self, big_cts: jax.Array) -> jax.Array:
        return keyswitch_fused(big_cts, self.ksk_hi, self.ksk_lo, self.params,
                               block_s=self.block_s, interpret=self.interpret)

    def blind_rotate(self, lut_glwes: jax.Array,
                     ms_cts: jax.Array) -> jax.Array:
        return blind_rotate_fused(lut_glwes, ms_cts, self.bsk_planes,
                                  self.params, dtype=self.dtype,
                                  block_f=self.block_f,
                                  interpret=self.interpret)

    def pbs_from_small(self, small_cts: jax.Array,
                       lut_polys: jax.Array) -> jax.Array:
        """PBS resumed after `keyswitch`: the KS-level-dedup half-round."""
        return pbs_small_fused(small_cts, lut_polys, self.bsk_planes,
                               self.params, dtype=self.dtype,
                               block_f=self.block_f,
                               interpret=self.interpret)

    # -- bandwidth accounting (gated by launch/roofline.py) -----------------
    @property
    def resident_key_bytes(self) -> tuple[int, int]:
        """(bsk_bytes, ksk_bytes) of the resident operands — what one
        fused round streams from HBM exactly once, regardless of B."""
        bsk = int(self.bsk_planes.size) * self.bsk_planes.dtype.itemsize
        ksk = (int(self.ksk_hi.size) + int(self.ksk_lo.size)) * 4
        return bsk, ksk

    def bytes_streamed_per_round(self, batch: int) -> int:
        """Key-reuse traffic model of ONE fused `lut_batch` round: the
        resident keys once, plus per-ciphertext input/LUT/output rows.
        `launch.roofline.pbs_round_model` computes the same quantity
        analytically; `benchmarks/kernels_bench.py` asserts this never
        exceeds that bound."""
        p = self.params
        bsk, ksk = self.resident_key_bytes
        per_ct = (2 * (p.big_n + 1) + p.N) * 8   # ct in + ct out + LUT poly
        return bsk + ksk + batch * per_ct
