"""Metric arithmetic shared by the readers in `metrics/`."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile: the smallest value with at least q% of the
    sample at or below it. A missing sample is `math.inf` (a frame that
    failed or was refused misses every limit); a percentile that lands on
    one has no finite value and is None, as is that of an empty sample."""
    if not values:
        return None
    ranked = sorted(values)
    v = ranked[max(0, math.ceil(q / 100 * len(ranked)) - 1)]
    return None if math.isinf(v) else v


def mean(values: list[float]) -> float | None:
    return sum(values) / len(values) if values else None
