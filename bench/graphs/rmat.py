"""R-MAT graphs (Chakrabarti, Zhan and Faloutsos, SDM 2004).

A configuration's ``graph`` names this generator with ``"generator":
"rmat"`` and gives ``log2_vertices``, ``draws``, ``graph_seed`` and the
initiator's ``a``, ``b``, ``c``.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def rmat_edges(log2_n: int, draws: int, seed: int,
               a: float, b: float, c: float) -> np.ndarray:
    """R-MAT edge draws as canonical ``(min, max)`` pairs, self-loops and
    repeats dropped, lexicographically sorted.

    The vertex labels are then permuted, as Graph500's generator does:
    R-MAT gives the hubs the lowest ids, which no real data set does, and
    the executors' id-ordered symmetry breaking would see that order.
    """
    rng = np.random.default_rng(seed)
    src = np.zeros(draws, np.int64)
    dst = np.zeros(draws, np.int64)
    for bit in range(log2_n):
        r = rng.random(draws)
        src |= (((r >= a + b) & (r < a + b + c)) | (r >= a + b + c)).astype(np.int64) << bit
        dst |= (((r >= a) & (r < a + b)) | (r >= a + b + c)).astype(np.int64) << bit
    perm = rng.permutation(1 << log2_n)
    keep = src != dst
    src, dst = perm[src[keep]], perm[dst[keep]]
    return np.unique(np.stack([np.minimum(src, dst), np.maximum(src, dst)], 1), axis=0)


def graph(g: dict) -> Tuple[int, np.ndarray]:
    """``(n, edges)`` of the configuration's ``graph`` block."""
    return 1 << g["log2_vertices"], rmat_edges(
        g["log2_vertices"], g["draws"], g["graph_seed"], g["a"], g["b"], g["c"])
