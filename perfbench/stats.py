"""Arithmetic from timelines to numbers (no JAX, no clock)."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """The p-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default), of a non-empty sequence."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    if len(xs) == 1:
        return float(xs[0])
    k = (len(xs) - 1) * p / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def tpot_s(token_times: Sequence[float]) -> Optional[float]:
    """Per-request mean gap between tokens: (last - first)/(tokens - 1);
    None for a request of fewer than two tokens."""
    if len(token_times) < 2:
        return None
    return (token_times[-1] - token_times[0]) / (len(token_times) - 1)


def due_in_window(due_s: Iterable[float], seconds: float) -> List[int]:
    """Indices of the requests due in [0, seconds)."""
    return [i for i, d in enumerate(due_s) if 0.0 <= d < seconds]
