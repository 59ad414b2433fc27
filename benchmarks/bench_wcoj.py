"""WCOJ executor vs binary join trees on dense patterns (K4/K5).

The paper's §V join trees blow up on cliques: intermediate match
tables grow super-linearly even when the final result is small, and
the padded device engine pays for that as match-cap-sized tensors.
The generic-join executor bounds every level by the observed prefix
sizes instead. Two row families over one planted near-clique graph
(n=4096 uniform background + a dense ER core — the regime where
Eq. 11's degree-moment estimates break and worst-case-optimality
matters):

- ``static/wcoj_vs_tree{,_k4}``: steady-state device listing
  (list + init_store execute, compile excluded) under each executor,
  both lossless — the tree side's caps are escalated in-run until its
  own overflow counters read zero, so the timing is never of a lossy
  configuration. **Hard gate**: WCOJ must beat the tree executor ≥2×
  on K5 (the ISSUE-10 acceptance bar).
- ``stream/wcoj_{k4,k5}``: per-batch ``advance()`` latency of the
  sharded streaming service maintaining the clique under
  ``executor="wcoj"`` — asserts zero cap overflow and zero store
  resizes across the run (AGM-bounded device memory, no resize loop).
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import Graph
from repro.core.pattern import PATTERN_LIBRARY
from repro.data.graphs import sample_update

from .common import Row, timeit

#: planted near-clique benchmark graph: flat-tail uniform background
#: (keeps deg_cap device-benchable) + a dense ER core that holds the
#: cliques. K4/K5 counts are in the thousands while background noise
#: contributes almost none.
N, M_BG, CORE_K, CORE_P = 4096, 12000, 32, 0.8


def planted_graph(seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    edges = set()
    while len(edges) < M_BG:
        a, b = int(rng.integers(N)), int(rng.integers(N))
        if a != b:
            edges.add((min(a, b), max(a, b)))
    core = rng.choice(N, size=CORE_K, replace=False)
    for i in range(CORE_K):
        for j in range(i + 1, CORE_K):
            if rng.random() < CORE_P:
                a, b = int(core[i]), int(core[j])
                edges.add((min(a, b), max(a, b)))
    return Graph.from_edges(np.array(sorted(edges), np.int64), n=N)


def _bench_static(rows):
    """Lossless steady-state listing, tree vs WCOJ, on one device."""
    import jax
    from jax.sharding import NamedSharding

    from repro.core.estimator import GraphStats
    from repro.core.match_engine import wcoj_level_counts
    from repro.core.storage import build_np_storage
    from repro.dist import jax_engine as je
    from repro.dist import sharded
    from repro.planner import CompileContext, compile_plan
    from repro.planner.sizing import quantize_store_caps
    from repro.stream.service import _default_caps

    g = planted_graph()
    storage = build_np_storage(g, 1)
    base = _default_caps(storage, g, 1)
    stats = GraphStats.of(g)
    mesh = jax.make_mesh((1,), ("data",))
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), sharded.partition_specs(mesh))

    def ctx(pattern, executor):
        return CompileContext(pattern=pattern, stats=stats, m=1, caps=base,
                              executor=executor)

    def pow2(x: int) -> int:
        n = 64
        while n < x:
            n *= 2
        return n

    def tree_time(pattern):
        """Escalate match/group/store caps until the tree listing is
        lossless, then time execute-only. The escalation itself is the
        story: the tree executor's intermediates outgrow any
        output-sized cap model on a dense core."""
        plan = compile_plan(ctx(pattern, "tree"))
        mc, store_g = 8192, plan.store_caps.group_cap
        while True:
            assert mc <= (1 << 22), "tree caps escalated past 4M rows"
            caps = je.EngineCaps(
                v_cap=base.v_cap, deg_cap=base.deg_cap, e_cap=base.e_cap,
                match_cap=mc, group_cap=max(base.group_cap, mc),
                set_cap=64, pair_cap=128)
            pt = jax.device_put(sharded.stack_partitions(storage, caps),
                                shardings)
            lstep = sharded.make_list_step(plan.program, mesh, caps)
            out, ldiag = lstep(pt)
            if int(ldiag["overflow"]):
                mc *= 4
                continue
            scaps = quantize_store_caps(sharded.StoreCaps(
                group_cap=store_g, set_cap=plan.store_caps.set_cap))
            istep = sharded.make_init_store_step(plan.program, mesh, caps,
                                                 scaps)
            _, idiag = istep(out)
            if int(idiag["overflow"]):
                store_g *= 2
                continue
            count = int(idiag["count"])

            def full():
                o, _ = lstep(pt)
                _, d = istep(o)
                jax.block_until_ready(d["count"])

            return timeit(full, repeat=3), count, mc, scaps.group_cap

    def wcoj_time(pattern):
        """Same protocol as ``ShardedBackend._register_wcoj``: a host
        calibration probe sizes every level from the observed prefix
        counts × level_headroom (1.5, transient tensors) and the store
        from × store_headroom (4.0, persistent state); no escalation
        loop needed."""
        plan = compile_plan(ctx(pattern, "wcoj"))
        observed = [wcoj_level_counts(part, plan.wcoj, anchor_to_centers=True)
                    for part in storage.parts]
        peaks = [max((o[i] for o in observed), default=0)
                 for i in range(len(plan.wcoj_level_caps))]
        lvl = tuple(pow2(int(1.5 * p)) for p in peaks)
        pt = jax.device_put(sharded.stack_partitions(storage, base), shardings)
        lstep = sharded.make_wcoj_list_step(pattern, plan.wcoj, mesh, base,
                                            lvl)
        scaps = quantize_store_caps(sharded.StoreCaps(
            group_cap=max(plan.store_caps.group_cap, pow2(int(4.0 * peaks[-1]))),
            set_cap=plan.store_caps.set_cap))
        istep = sharded.make_wcoj_init_store_step(pattern, plan.ord, mesh,
                                                  base, scaps, lvl)
        out, ldiag = lstep(pt)
        _, idiag = istep(out)
        ovf = int(ldiag["overflow"]) + int(idiag["overflow"])
        assert not ovf, f"calibrated WCOJ caps overflowed ({ovf})"
        count = int(idiag["count"])

        def full():
            o, _ = lstep(pt)
            _, d = istep(o)
            jax.block_until_ready(d["count"])

        return timeit(full, repeat=3), count, lvl, scaps.group_cap

    for pname, suffix, gate in (("q6_clique5", "", True),
                                ("q4_clique4", "_k4", False)):
        pattern = PATTERN_LIBRARY[pname]
        t_tree, n_tree, mc, sg = tree_time(pattern)
        t_wcoj, n_wcoj, lvl, wsg = wcoj_time(pattern)
        assert n_tree == n_wcoj, (pname, n_tree, n_wcoj)
        ratio = t_tree / t_wcoj
        rows.append(Row(
            f"static/wcoj_vs_tree{suffix}", t_wcoj * 1e6,
            f"count={n_wcoj};tree_us={int(t_tree * 1e6)};"
            f"speedup_x1000={int(ratio * 1000)};tree_match_cap={mc};"
            f"tree_store_g={sg};wcoj_caps={'/'.join(map(str, lvl))};"
            f"wcoj_store_g={wsg}"))
        if gate and ratio < 2.0:
            raise RuntimeError(
                f"WCOJ acceptance failed on {pname}: wcoj "
                f"{t_wcoj * 1e6:.0f}us vs tree {t_tree * 1e6:.0f}us — "
                f"{ratio:.2f}x < the required 2x")


def _bench_stream(rows):
    """Incremental maintenance under executor='wcoj': delta-seeded
    generic-join patches through the fused megastep. Hard-asserts that
    the n=4096 run never overflows a cap or enters the store-resize
    loop — the calibrated level caps ARE the memory bound."""
    from repro.stream import BatchScheduler, ListingService

    for pname, rname in (("q4_clique4", "stream/wcoj_k4"),
                         ("q6_clique5", "stream/wcoj_k5")):
        g = planted_graph()
        svc = ListingService(
            g, backend="sharded", max_add=16, max_del=16, executor="wcoj",
            audit_every=0, scheduler=BatchScheduler(max_ops=32))
        n0 = svc.register(pname, PATTERN_LIBRARY[pname])
        entry = svc.backend.entries[pname]
        overflow = 0
        lat = []
        for b in range(4):
            upd = sample_update(svc.projected_graph(), 8, 8, seed=100 + b)
            svc.ingest(upd)
            t0 = time.perf_counter()
            batches = svc.advance()
            dt = time.perf_counter() - t0
            overflow += sum(bm.overflow for bm in batches)
            if b > 0:                    # batch 0 pays the megastep compile
                lat.append(dt / max(len(batches), 1))
        assert overflow == 0, f"{pname}: device cap overflow ({overflow})"
        assert svc.backend.store_resizes == 0, \
            f"{pname}: store resize loop ({svc.backend.store_resizes})"
        rows.append(Row(
            rname, float(np.mean(lat)) * 1e6,
            f"count0={n0};count={svc.count(pname)};overflow=0;"
            f"store_resizes=0;level_caps="
            f"{'/'.join(map(str, entry.wcoj_level_caps))};"
            f"store_g={entry.store_caps.group_cap}"))


def run() -> list:
    rows = []
    _bench_static(rows)
    _bench_stream(rows)
    return rows
