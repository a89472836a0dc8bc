"""Shared order statistics for serving/bench reporting.

One percentile implementation for the whole repo (DESIGN.md §9):
``Engine.stats()`` and the benchmark helpers all used to carry their
own nearest-rank variants, which disagree with each other (and with numpy) on small
samples — exactly the regime a p99 over a dozen requests lives in.
This one linearly interpolates between closest ranks, matching
``numpy.percentile(..., method='linear')`` bit-for-bit (asserted in
``tests/test_telemetry.py``), and returns NaN on empty input instead of
raising so reporting code never has to special-case a drained engine.
"""
from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentile(xs: Iterable[float], q: float) -> float:
    """q-th percentile (``q`` in [0, 100]) with linear interpolation
    between closest ranks; NaN for an empty sample."""
    xs = sorted(xs)
    if not xs:
        return float("nan")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile q={q} outside [0, 100]")
    rank = (q / 100.0) * (len(xs) - 1)
    lo = math.floor(rank)
    hi = math.ceil(rank)
    if lo == hi:
        return float(xs[lo])
    frac = rank - lo
    return float(xs[lo] * (1.0 - frac) + xs[hi] * frac)


def percentiles(xs: Sequence[float], qs: Iterable[float]) -> dict:
    """Several percentiles of one (sorted-once) sample: {q: value}."""
    xs = sorted(xs)
    return {q: percentile(xs, q) for q in qs}
