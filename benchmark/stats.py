"""Arithmetic on latency samples. Pure Python: the load generator's child
process imports this and must never pull in JAX or the program."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics, as ``numpy.percentile`` does by default. ``None``
    for no samples; ``inf`` samples (failed requests) sort last, so a
    failure is the worst latency."""
    ordered = sorted(values)
    if not ordered:
        return None
    rank = (len(ordered) - 1) * q / 100.0
    low = math.floor(rank)
    high = min(low + 1, len(ordered) - 1)
    if ordered[low] == ordered[high]:            # also inf - inf
        return ordered[low]
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def tpot_ms(first_s: float, last_s: float, frames: int) -> Optional[float]:
    """Time per output token of one stream: (last frame - first frame)
    over the frames after the first. Not the raw gap between frames: a
    K-step tick delivers K frames at once, so the median raw gap is ~0."""
    if frames < 2:
        return None
    return (last_s - first_s) / (frames - 1) * 1e3


def mean(values: Sequence[float]) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None


def finite(values: Iterable[Optional[float]]) -> List[float]:
    return [v for v in values if v is not None and math.isfinite(v)]
