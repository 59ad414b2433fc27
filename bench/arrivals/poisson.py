"""Poisson arrivals, at a constant rate or under a repeating rate profile.

A traffic file with ``"arrivals": "poisson"`` may give ``"profile"``: a
list of ``[seconds, multiplier]`` segments, repeated over the window, that
scales the offered rate (bursts, lulls). Without it the rate is constant.
"""

from __future__ import annotations

import numpy as np


def due_times(traffic: dict, rate: float, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    """Arrival times in ``[0, seconds)`` at a mean of ``rate`` ops/s.

    The gaps are the exponential distribution's quantiles at ``(i + ½)/N``
    for ``N`` the expected number of arrivals, in an order drawn from
    ``rng``: the gaps of a Poisson process, with the same multiset of gaps
    (so the same number of arrivals and the same span) for every seed. A
    profile maps them through the integrated rate, so the process is then
    a Poisson process of that varying rate.
    """
    profile = traffic.get("profile")
    if not profile:
        n = int(round(rate * seconds))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        rng.shuffle(gaps)
        due = np.cumsum(gaps)
        return due[due < seconds]
    seg = np.array(profile, np.float64).reshape(-1, 2)
    if np.any(seg[:, 0] <= 0) or np.any(seg[:, 1] < 0):
        raise ValueError(f"profile segments need positive lengths and rates: {profile}")
    # The profile's corners over the window; its mean multiplier is 1.
    mult = seg[:, 1] / (np.dot(seg[:, 0], seg[:, 1]) / seg[:, 0].sum())
    reps = int(np.ceil(seconds / seg[:, 0].sum())) + 1
    t = np.concatenate([[0.0], np.cumsum(np.tile(seg[:, 0], reps))])
    work = np.concatenate([[0.0], np.cumsum(np.tile(seg[:, 0] * mult, reps))]) * rate
    n = int(round(np.interp(seconds, t, work)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    rng.shuffle(gaps)
    due = np.interp(np.cumsum(gaps), work, t)
    return due[due < seconds]
