"""Medians and percentiles of a window, with the rule for tails."""

from __future__ import annotations

import math
import re

# a percentile needs at least ten samples beyond it (choosing-metrics, 1)
MIN_BEYOND = 10


class TooFewSamples(Exception):
    pass


def min_samples(q: float) -> int:
    """Samples a window must hold for its q-th percentile to count."""
    return math.ceil(MIN_BEYOND / (1.0 - q / 100.0))


def percentile(values, q: float, strict: bool = True) -> float:
    """Nearest-rank percentile. With `strict`, refuses a tail that fewer
    than MIN_BEYOND samples lie beyond: p99 needs 1,000, p95 needs 200."""
    vals = sorted(values)
    if not vals:
        raise TooFewSamples("no samples")
    if strict and q > 50.0 and len(vals) < min_samples(q):
        raise TooFewSamples(
            f"p{q:g} of {len(vals)} samples: needs {min_samples(q)}")
    i = min(int(math.ceil(len(vals) * q / 100.0)) - 1, len(vals) - 1)
    return float(vals[max(i, 0)])


def median(values) -> float:
    vals = sorted(values)
    if not vals:
        raise TooFewSamples("no samples")
    mid = len(vals) // 2
    return float(vals[mid] if len(vals) % 2 else (vals[mid - 1] + vals[mid]) / 2)


def named_percentile(name: str, values):
    """The value of a metric named `p<q>_ms` (p95_ms, p99.9_ms): that
    percentile of `values`. None for another name, and where the window
    holds too few samples for it: the metric is then left out of the line."""
    q = re.match(r"^p(\d+(?:\.\d+)?)_ms$", name)
    if not q:
        return None
    try:
        return percentile(values, float(q.group(1)))
    except TooFewSamples:
        return None
