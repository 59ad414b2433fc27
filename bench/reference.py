"""Plain host reference: exact counts of any pattern, given as its edge list.

Independent of the code under test: it imports nothing of ``repro``, and a
pattern reaches it as the edge list of ``bench/patterns/<name>.json``. A
pattern is counted as the service lists it: distinct subgraphs (edge sets)
isomorphic to the pattern, not necessarily induced.

The counts follow the graph op by op. An inserted edge adds the subgraphs
through it, a deleted one takes them away; those are found by backtracking
from the edge. Every directed pattern edge ``(a, b)`` is pinned to the data
edge ``(u, v)`` in turn: an embedding whose image holds ``{u, v}`` maps
exactly one pattern edge onto it, in one direction, so it is counted once,
and ``|Aut(P)|`` embeddings share one subgraph. Directed pattern edges in
one orbit of ``Aut(P)`` pin the same number of embeddings, so one of each
orbit is searched.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Sequence, Set, Tuple

import numpy as np

Edge = Tuple[int, int]


class PatternShape:
    """A connected pattern: its vertices ``0..k-1`` and edges."""

    def __init__(self, edges: Sequence[Sequence[int]]):
        pairs = {(min(a, b), max(a, b)) for a, b in edges}
        if not pairs or any(a == b for a, b in pairs):
            raise ValueError(f"not a simple pattern: {edges}")
        labels = sorted({v for e in pairs for v in e})
        relabel = {v: i for i, v in enumerate(labels)}
        self.k = len(labels)
        self.edges = sorted((relabel[a], relabel[b]) for a, b in pairs)
        self.adj: List[Set[int]] = [set() for _ in range(self.k)]
        for a, b in self.edges:
            self.adj[a].add(b)
            self.adj[b].add(a)
        own = set(self.edges)
        self.autos = [p for p in itertools.permutations(range(self.k))
                      if all((min(p[a], p[b]), max(p[a], p[b])) in own for a, b in self.edges)]
        self.plans = self._plans()

    def _plans(self) -> List[Tuple[int, List[Tuple[int, List[int]]]]]:
        """``(orbit size, search order)`` per orbit of directed edges.

        The order starts with the pinned ``a, b``; each later vertex has a
        placed neighbour (the pattern is connected) and lists them all.
        """
        directed = [(a, b) for a, b in self.edges] + [(b, a) for a, b in self.edges]
        seen: Set[Edge] = set()
        plans = []
        for a, b in directed:
            if (a, b) in seen:
                continue
            orbit = {(p[a], p[b]) for p in self.autos}
            seen |= orbit
            placed, steps = [a, b], []
            while len(placed) < self.k:
                x = max((v for v in range(self.k) if v not in placed),
                        key=lambda v: (len(self.adj[v] & set(placed)), -v))
                nbrs = [placed.index(y) for y in self.adj[x] if y in placed]
                if not nbrs:
                    raise ValueError("pattern is not connected")
                steps.append((x, nbrs))
                placed.append(x)
            plans.append((len(orbit), steps))
        return plans

    def key(self) -> Tuple[Edge, ...]:
        """A label-free form: the least sorted edge list over relabellings."""
        return min(tuple(sorted((min(p[a], p[b]), max(p[a], p[b])) for a, b in self.edges))
                   for p in itertools.permutations(range(self.k)))


class Counter:
    """Exact counts of ``patterns`` over a graph that changes edge by edge."""

    def __init__(self, n: int, edges: np.ndarray, patterns: Dict[str, PatternShape]):
        self.adj: List[Set[int]] = [set() for _ in range(n)]
        self.patterns = patterns
        edges = np.asarray(edges, np.int64).reshape(-1, 2)
        for u, v in edges.tolist():
            self.adj[u].add(v)
            self.adj[v].add(u)
        self.counts = {name: 0 for name in patterns}
        for name, p in patterns.items():
            total = sum(self._embeddings_through(p, u, v) for u, v in edges.tolist())
            # every subgraph holds |E(P)| edges and is |Aut(P)| embeddings
            self.counts[name] = total // (len(p.edges) * len(p.autos))

    def _embeddings_through(self, p: PatternShape, u: int, v: int) -> int:
        total = 0
        for orbit, steps in p.plans:
            total += orbit * self._search(steps, [u, v])
        return total

    def _search(self, steps, placed: List[int]) -> int:
        if len(placed) == len(steps) + 2:
            return 1
        _, nbrs = steps[len(placed) - 2]
        cand = self.adj[placed[nbrs[0]]]
        for i in nbrs[1:]:
            cand = cand & self.adj[placed[i]]
        found = 0
        for w in cand:
            if w not in placed:
                placed.append(w)
                found += self._search(steps, placed)
                placed.pop()
        return found

    def through(self, name: str, u: int, v: int) -> int:
        """Subgraphs through the present edge ``(u, v)``."""
        p = self.patterns[name]
        return self._embeddings_through(p, u, v) // len(p.autos)

    def insert(self, u: int, v: int) -> None:
        if u == v or v in self.adj[u]:
            return
        self.adj[u].add(v)
        self.adj[v].add(u)
        for name in self.counts:
            self.counts[name] += self.through(name, u, v)

    def delete(self, u: int, v: int) -> None:
        if v not in self.adj[u]:
            return
        for name in self.counts:
            self.counts[name] -= self.through(name, u, v)
        self.adj[u].discard(v)
        self.adj[v].discard(u)
