"""Arithmetic of the end-to-end metrics: rate, percentiles, spread.

Kept with the benchmark so that every later PR computes the same
numbers in the same way.
"""

from __future__ import annotations

import statistics
from typing import Sequence

import numpy as np


def update_rate(window_start: float, commits: Sequence[tuple]) -> float:
    """Ops committed in the window over the time from the window's start
    to its last commit.

    ``commits`` holds ``(commit_time, n_ops)`` of every batch started in
    the window, in commit order; the window ends on the last of them, so
    no idle tail after the last commit dilutes the rate.
    """
    if not commits:
        raise ValueError("no batch committed in the window")
    ops = sum(n for _, n in commits)
    end = commits[-1][0]
    if end <= window_start:
        raise ValueError("last commit precedes the window's start")
    return ops / (end - window_start)


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0–100), linearly interpolated between
    closest ranks (numpy's default method)."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(v, q))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(n=4)``."""
    q = statistics.quantiles(list(values), n=4)
    return (q[2] - q[0]) / statistics.median(values)
