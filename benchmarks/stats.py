"""The arithmetic of the end-to-end metrics, on plain numbers."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in [0, 100]; NaN for no values."""
    vals = sorted(v for v in values if not math.isnan(v))
    if not vals:
        return float("nan")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[min(rank, len(vals)) - 1]


def spread(values) -> float:
    """Distance between the first and third quartile over the median,
    as the contract measures a metric's run-to-run spread."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def train_tokens_per_s(tokens_per_step: int, step_ends_s) -> float:
    """All the tokens of all the steps over all the time of the window:
    ``step_ends_s`` are the times, from the window's start, at which
    the host knew each step ended (``block_until_ready`` returned); the
    last is the window's end."""
    if not step_ends_s:
        return float("nan")
    return tokens_per_step * len(step_ends_s) / step_ends_s[-1]
