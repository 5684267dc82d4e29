"""Roofline-term extraction from compiled dry-run artifacts (deliverable g).

Hardware model: TPU v5e —
    197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.

    compute term    = HLO_FLOPs   / (chips * PEAK_FLOPS)
    memory term     = HLO_bytes   / (chips * HBM_BW)
    collective term = coll_bytes  / (chips * ICI_BW)

``compiled.cost_analysis()`` ignores while-loop trip counts (scan bodies
counted once), so the terms here come from `repro.launch.hlo_analysis`,
which re-derives loop-weighted per-device FLOPs / HBM bytes / collective
bytes from the compiled HLO text.  All analyzer numbers are PER DEVICE;
the formulas below therefore divide by per-chip peaks only.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

from repro.launch import hlo_analysis

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

@dataclasses.dataclass
class Roofline:
    flops: float               # per-device HLO flops (loop-weighted)
    hbm_bytes: float           # per-device bytes accessed (loop-weighted)
    coll_bytes: float          # per-device collective bytes
    chips: int
    coll_breakdown: dict
    model_flops: float = 0.0   # global 6*N*D (or 6*N_active*D)
    hbm_bytes_major: float = 0.0  # perfectly-fused-elementwise bound

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def t_memory_major(self) -> float:
        """Optimistic memory term: only dot/gather/scatter/DUS-bearing ops
        touch HBM (elementwise perfectly fused).  A TPU backend lands
        between this and t_memory."""
        return self.hbm_bytes_major / HBM_BW

    @property
    def t_bound_major(self) -> float:
        return max(self.t_compute, self.t_memory_major, self.t_collective)

    @property
    def mfu_bound_major(self) -> float:
        t_useful = self.model_flops / (self.chips * PEAK_FLOPS)
        return t_useful / self.t_bound_major if self.t_bound_major else 0.0

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def mfu_bound(self) -> float:
        """Upper bound on achievable MFU at this lowering: useful-FLOPs
        time / roofline-dominant time."""
        t_useful = self.model_flops / (self.chips * PEAK_FLOPS)
        return t_useful / self.t_bound if self.t_bound else 0.0

    @property
    def flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful.
        >1 would mean undercounting; <1 indicates remat/halo/dedup waste."""
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes, "chips": self.chips,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_memory_major_s": self.t_memory_major,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck, "mfu_bound": self.mfu_bound,
            "mfu_bound_major": self.mfu_bound_major,
            "flops_ratio": self.flops_ratio,
            "coll_breakdown": self.coll_breakdown,
        }


def model_flops(cfg, shape) -> float:
    """6*N*D for training, 2*N*D for a forward pass/prefill, 2*N_active per
    decoded token (D = tokens processed)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    # decode: one token per sequence; attention reads the whole KV cache —
    # count the matmul FLOPs only (2*N_active per token)
    return 2.0 * n_active * shape.global_batch


@dataclasses.dataclass
class PbsRoundModel:
    """Analytic per-round traffic/bandwidth model of one fused `lut_batch`
    round — the bound that gates the Pallas engine-room win.

    `fused_bytes` is the paper's key-reuse traffic: the evaluation keys
    stream from HBM ONCE per round regardless of batch size, plus O(B)
    ciphertext/LUT rows.  `unfused_bytes` re-streams the keys per
    ciphertext (the Morphling-XPU baseline, `lut_batch_xpu`).  The
    measured `FusedPbsPack.bytes_streamed_per_round` must never exceed
    `fused_bytes` (asserted by `benchmarks/kernels_bench.py`) — if it
    does, the residency contract broke and the speedup story with it.
    """
    bsk_bytes: int
    ksk_bytes: int
    ct_in_bytes: int           # one (big_n+1) u64 row
    ct_out_bytes: int
    lut_bytes: int             # one (N,) u64 test polynomial
    batch: int

    @property
    def key_bytes(self) -> int:
        return self.bsk_bytes + self.ksk_bytes

    @property
    def per_ct_bytes(self) -> int:
        return self.ct_in_bytes + self.ct_out_bytes + self.lut_bytes

    @property
    def fused_bytes(self) -> int:
        """Keys once + per-ciphertext rows (key-reuse residency)."""
        return self.key_bytes + self.batch * self.per_ct_bytes

    @property
    def unfused_bytes(self) -> int:
        """Keys re-streamed per ciphertext (no reuse baseline)."""
        return self.batch * (self.key_bytes + self.per_ct_bytes)

    @property
    def reuse_factor(self) -> float:
        return self.unfused_bytes / self.fused_bytes

    @property
    def t_memory(self) -> float:
        """HBM-bound wall clock of one fused round on the v5e model."""
        return self.fused_bytes / HBM_BW

    @property
    def arithmetic_intensity_keys(self) -> float:
        """MAC ops per key byte — scales with B under residency."""
        return float(self.batch) / max(self.key_bytes, 1)

    def to_dict(self) -> dict:
        return {
            "bsk_bytes": self.bsk_bytes, "ksk_bytes": self.ksk_bytes,
            "per_ct_bytes": self.per_ct_bytes, "batch": self.batch,
            "fused_bytes": self.fused_bytes,
            "unfused_bytes": self.unfused_bytes,
            "reuse_factor": self.reuse_factor,
            "t_memory_s": self.t_memory,
        }


def pbs_round_model(params, batch: int) -> PbsRoundModel:
    """Build the per-round bandwidth model from TFHE parameters.

    Key bytes match `TaurusEngine.key_bytes` exactly: the Fourier BSK is
    (n, 2, (k+1)*level, k+1, N/2) float64 planes and the KSK is
    (big_n, ks_level, n+1) uint64 — the fused pack's f64 planes and
    (hi, lo) uint32 limbs hold the same bytes, so reference and pallas
    engines share one model.
    """
    n, k, N = params.n, params.k, params.N
    bsk = n * (k + 1) * params.pbs_level * (k + 1) * (N // 2) * 16
    ksk = params.big_n * params.ks_level * (n + 1) * 8
    ct = (params.big_n + 1) * 8
    return PbsRoundModel(bsk_bytes=bsk, ksk_bytes=ksk, ct_in_bytes=ct,
                         ct_out_bytes=ct, lut_bytes=N * 8, batch=batch)


def from_compiled(compiled, chips: int, mflops: float) -> Roofline:
    costs = hlo_analysis.analyze(compiled.as_text())
    return Roofline(
        flops=costs.flops, hbm_bytes=costs.hbm_bytes,
        coll_bytes=costs.coll_bytes, chips=chips,
        coll_breakdown=costs.coll_breakdown, model_flops=mflops,
        hbm_bytes_major=costs.hbm_bytes_major)
