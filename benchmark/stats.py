"""The arithmetic every reported number goes through."""
from __future__ import annotations

import statistics
from typing import Iterable, Optional, Sequence


def median(values: Iterable[float]) -> float:
    return float(statistics.median(list(values)))


def quartile_spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median, quartiles as `statistics.quantiles(values, n=4)` gives them:
    the spread the driver judges a bound against."""
    xs = [float(v) for v in values]
    if len(xs) < 2:
        return None
    q1, _, q3 = statistics.quantiles(xs, n=4)
    med = statistics.median(xs)
    return (q3 - q1) / abs(med) if med else None


def rate_over_window(work: float, window_seconds: float) -> float:
    """Work per second over the whole window: all the work it held over
    all its time, so that a stall in any reading is in the number."""
    return work / window_seconds
