"""Percentile arithmetic: linear interpolation between closest ranks, the
rule ``numpy.percentile`` uses by default and that
``repro.serving.metrics.summarize`` applies to its TTFT tails."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) of ``values``; nan when empty."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values) -> float:
    xs = list(values)
    return sum(xs) / len(xs) if xs else float("nan")
