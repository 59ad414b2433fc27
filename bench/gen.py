"""Graph and traffic generation: the one generator that every traffic
file parameterises.

The graph is the configuration's data set, made by the generator its
``graph`` block names (``bench/graphs/<generator>.py``) from the
configuration's own ``graph_seed``: the same in every run, as a published
data set would be. ``--seed`` draws the traffic: which edges are deleted,
which are inserted, in what order and, open-loop, when (the arrival law a
traffic file names, ``bench/arrivals/<law>.py``). (A graph relabelled per
seed was tried: registration's work depends on vertex order, so the seed
changed the work; see PERF.md.)

Updates follow the paper's §VII-C protocol: half deletions of present
edges chosen uniformly, half insertions of absent edges, their endpoints
uniform or, with ``"insert_endpoints": "degree"``, drawn in proportion to
degree (hub-skewed inserts). The semantics of
``repro.data.graphs.sample_update`` and ``rmat_graph`` are copied here
rather than imported: the yardstick has to stay fixed while later changes
edit the program, and a generator imported from it would move every
cell's traffic with them.
"""

from __future__ import annotations

import os
from typing import Dict, List, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
OP_ADD, OP_DELETE = 1, -1


def load_named(kind: str, name: str, bench_dir: str = BENCH):
    """The module ``bench/<kind>/<name>.py``: a graph generator, an arrival
    law or a metric reader, found by the name a file gives."""
    import importlib.util

    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise LookupError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_graph(cfg: dict, bench_dir: str = BENCH) -> Tuple[int, np.ndarray]:
    """``(n, edges)`` of the configuration's graph, made by the generator
    its ``graph`` block names (``bench/graphs/<generator>.py``)."""
    g = cfg["graph"]
    return load_named("graphs", g.get("generator", "rmat"), bench_dir).graph(g)


def graph_statistics(n: int, edges: np.ndarray) -> Dict[str, float]:
    """The shape of a graph, as the configurations state it beside their
    source's published figures: average degree, average clustering
    coefficient (over all vertices, and over those with an edge),
    triangles per edge, the largest degree as a share of the vertices,
    and the share of vertices with no edge."""
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    adj: List[set] = [set() for _ in range(n)]
    for u, v in edges.tolist():
        adj[u].add(v)
        adj[v].add(u)
    deg = np.array([len(a) for a in adj], np.float64)
    tri_at = np.zeros(n)
    for u, v in edges.tolist():
        t = len(adj[u] & adj[v])
        tri_at[u] += t
        tri_at[v] += t
    tri_at /= 2
    wedges = deg * (deg - 1) / 2
    cc = np.divide(tri_at, wedges, out=np.zeros(n), where=wedges > 0)
    m = edges.shape[0]
    return {"average_degree": 2 * m / n,
            "average_clustering": float(cc.mean()),
            "average_clustering_of_linked": float(cc[deg > 0].mean()),
            "triangles_per_edge": float(tri_at.sum() / 3 / m),
            "max_degree_share": float(deg.max() / n),
            "isolated_share": float(np.mean(deg == 0))}


def codes_of(edges: np.ndarray) -> np.ndarray:
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    return (edges.min(1) << 32) | edges.max(1)


class OpStream:
    """The §VII-C update stream as single edge operations, in blocks.

    A block of ``block`` ops holds ``round(block * delete_share)``
    deletions of edges present when the block starts and insertions of
    edges absent then, in an order drawn from the seed. Any run of
    consecutive ops inside one block is a well-formed update against the
    graph that all earlier ops made.
    """

    def __init__(self, n: int, edges: np.ndarray, block: int,
                 rng: np.random.Generator, delete_share: float = 0.5,
                 insert_endpoints: str = "uniform"):
        if insert_endpoints not in ("uniform", "degree"):
            raise ValueError(f"unknown insert_endpoints {insert_endpoints!r}")
        self.n = n
        self.by_degree = insert_endpoints == "degree"
        self.block = int(block)
        self.n_del = int(round(self.block * delete_share))
        self.rng = rng
        self.present: List[int] = [int(c) for c in codes_of(edges)]
        self.index = {c: i for i, c in enumerate(self.present)}

    def _remove(self, i: int) -> int:
        code = self.present[i]
        last = self.present.pop()
        if i < len(self.present):
            self.present[i] = last
            self.index[last] = i
        del self.index[code]
        return code

    def _endpoints(self) -> Tuple[int, int]:
        if not self.by_degree:
            return tuple(int(x) for x in self.rng.integers(self.n, size=2))
        # An end of a present edge drawn uniformly: a vertex in proportion
        # to its degree.
        ends = []
        for i, side in zip(self.rng.integers(len(self.present), size=2),
                           self.rng.integers(2, size=2)):
            code = self.present[int(i)]
            ends.append(int(code >> 32) if side else int(code & 0xFFFFFFFF))
        return ends[0], ends[1]

    def next_block(self) -> Tuple[np.ndarray, np.ndarray]:
        """``(kinds, codes)`` of the next block, ``kinds`` in
        ``{OP_ADD, OP_DELETE}``."""
        kinds = np.array([OP_DELETE] * self.n_del + [OP_ADD] * (self.block - self.n_del),
                         np.int64)
        self.rng.shuffle(kinds)
        codes = np.empty(self.block, np.int64)
        deleted, added = set(), []
        for j, k in enumerate(kinds):
            if k == OP_DELETE:
                # Only edges present at the block's start: this block's
                # inserts join the pool when it ends.
                code = self._remove(int(self.rng.integers(len(self.present))))
                deleted.add(code)
            else:
                while True:
                    u, v = self._endpoints()
                    if u == v:
                        continue
                    code = (min(u, v) << 32) | max(u, v)
                    if code not in self.index and code not in deleted and code not in added:
                        break
                added.append(code)
            codes[j] = code
        for code in added:
            self.index[code] = len(self.present)
            self.present.append(code)
        return kinds, codes
