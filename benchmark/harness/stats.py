"""The benchmark's arithmetic on samples: percentiles, medians, rates."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile, q in (0, 100]; NaN on no samples."""
    if not values:
        return math.nan
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def median(values: Sequence[float]) -> float:
    return float(statistics.median(values)) if values else math.nan


def device_memory_peak(devices) -> int:
    """Peak bytes held on the fullest chip, from the runtime's own
    counters: ``peak_bytes_in_use`` (live arrays) plus
    ``peak_bytes_reserved`` (what the runtime set aside for running
    programs' scratch; on a v5e the live counter leaves it out).  The
    two peaks need not fall together, so the sum is an upper bound."""
    def one(d):
        st = d.memory_stats() or {}
        return (int(st.get("peak_bytes_in_use", 0))
                + int(st.get("peak_bytes_reserved", 0)))
    return max((one(d) for d in devices), default=0)
