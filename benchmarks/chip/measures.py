"""Statistics shared by the metric readers."""
from __future__ import annotations

import math


def quantile(values, q: float) -> float | None:
    """Nearest-rank quantile: the smallest value with at least a share q of
    the values at or below it (a real sample, never an interpolation).
    None for no values or where it falls on a request never answered."""
    vals = sorted(values)
    if not vals:
        return None
    v = vals[max(0, math.ceil(q * len(vals)) - 1)]
    return v if math.isfinite(v) else None


def idle_share(trace: dict | None) -> float | None:
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
