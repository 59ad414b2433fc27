#!/usr/bin/env python3
"""Bring-up smoke test: the sharded listing service end to end on a TPU.

    python chip_smoke.py             # one chip: device, kernel and service phases
    python chip_smoke.py --chips 4   # four chips: only the multi-device service phase

Phases (each prints its own ``[phase]`` lines):

- **device** — platform, device kind and count. Anything but a TPU (a
  CPU-only JAX, ``JAX_PLATFORMS=cpu``) is a failure, never a fallback.
- **kernels** — ``member_probe`` and ``set_intersect`` compiled (not
  interpreted) at the shapes the served path hands them, against the
  ``repro.kernels.ref`` oracles.
- **service** — ``ListingService(backend="sharded")`` over an R-MAT
  stand-in for the paper's web graphs, with a triangle subscription on
  the join-tree executor (VCBC-compressed unit listing, Nav-join patches
  over the unit-table carry) and a 4-clique one on the generic-join
  (WCOJ) executor, fed the §VII-C update mix (half deletes, half inserts)
  through ``ingest``/``advance``. At every committed watermark the live
  counts must equal a plain host reference (an independent
  degree-ordered counter over the replayed edge set, below) and every
  overflow counter must be zero.

The last stdout line is the result, and is printed only when every
phase passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failure exits non-zero without it. The script runs in one process
and starts no other: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# The service graph: R-MAT (the power-law family of benchmarks/common.py)
# at 2^LOG2 vertices and DRAWS edge draws: UK~, the largest graph any
# benchmark used before and the floor for this check. R-MAT(14, 100_000)
# also fits one v5e's HBM when compiled for it, but at UK~ a cold run
# already spends most of the 20-minute budget compiling and listing.
LOG2, DRAWS, SEED = 13, 48_000, 3
# Pattern → executor: one subscription on each of the service's two
# listing/maintenance paths.
PATTERNS = {"q2_triangle": "tree", "q4_clique4": "wcoj"}
BATCHES = 6
BATCH_OPS = 8             # per batch: half deletes, half inserts (§VII-C)


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Plain host reference: triangle and 4-clique counts of an edge set
# ---------------------------------------------------------------------------

def reference_counts(n: int, edges: np.ndarray) -> dict:
    """Exact triangle and 4-clique counts of a simple undirected graph.

    Independent of the code under test: orient every edge from lower to
    higher (degree, id) rank, so each clique is counted once at its
    lowest-ranked vertex; triangles are common out-neighbors of an
    oriented edge, 4-cliques oriented edges inside that common set.
    """
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    deg = np.bincount(edges.ravel(), minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    a, b = edges[:, 0], edges[:, 1]
    swap = rank[a] > rank[b]
    src = np.where(swap, b, a)
    dst = np.where(swap, a, b)
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    ptr = np.searchsorted(src, np.arange(n + 1))
    codes = src * n + dst                      # sorted: oriented-edge lookup
    tri = k4 = 0
    for u in range(n):
        nu = dst[ptr[u]:ptr[u + 1]]
        for v in nu:
            w = np.intersect1d(nu, dst[ptr[v]:ptr[v + 1]], assume_unique=True)
            tri += w.shape[0]
            if w.shape[0] > 1:
                i, j = np.triu_indices(w.shape[0], 1)
                x, y = w[i], w[j]
                lo = np.where(rank[x] < rank[y], x, y)
                hi = np.where(rank[x] < rank[y], y, x)
                q = lo * n + hi
                pos = np.minimum(np.searchsorted(codes, q), codes.shape[0] - 1)
                k4 += int(np.count_nonzero(codes[pos] == q))
    return {"q2_triangle": int(tri), "q4_clique4": int(k4)}


def _pow2(x: float) -> int:
    return 1 << max(int(np.ceil(np.log2(max(x, 1.0)))), 0)


def service_caps(graph, m: int, triangles: int):
    """Engine caps for the smoke's subscriptions on ``m`` devices.

    The service's defaults (storage caps from the built partitions) with
    the table caps raised to hold the join-tree triangle listing, which
    lists every triangle at its anchor and compresses the third vertex
    into per-edge sets: rows ≤ triangles, groups ≤ edges, and a set no
    wider than the largest common neighborhood of an edge — each with
    ×1.25 headroom, on the power-of-two grid.
    """
    from repro.core import build_np_storage
    from repro.stream.service import _default_caps

    nbr = [graph.indices[graph.indptr[v]:graph.indptr[v + 1]] for v in range(graph.n)]
    codeg = max((np.intersect1d(nbr[a], nbr[b], assume_unique=True).shape[0]
                 for a, b in graph.edges().tolist()), default=0)
    caps = _default_caps(build_np_storage(graph, m), graph, m)
    return dataclasses.replace(
        caps, match_cap=max(caps.match_cap, _pow2(1.25 * triangles)),
        group_cap=max(caps.group_cap, _pow2(1.25 * graph.codes.shape[0])),
        set_cap=max(caps.set_cap, _pow2(1.25 * codeg)))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def device_phase(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    _check(d.platform == "tpu",
           f"platform is {d.platform!r}, not 'tpu' — refusing to fall back")
    _check(len(devs) == chips, f"need {chips} device(s), JAX sees {len(devs)}")
    return info


def _time(fn, *args):
    import jax

    jax.block_until_ready(fn(*args))          # compile + warm
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def kernel_phase(graph, caps) -> None:
    """Compiled kernels vs the pure-jnp oracles at served-path shapes:
    edge-existence probes of one extension block (``2^20`` candidates)
    against the partition's edge table (``e_cap``) and against a batch's
    deletes, and the CC-join set intersection at the engine's
    ``group_cap × set_cap``. Each probe is also timed on the engine's
    binary search, the path ``use_pallas=False`` takes."""
    import jax
    import jax.numpy as jnp

    from repro.dist import jax_engine as je
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)
    edges = graph.edges().astype(np.int32)          # lex-sorted (min, max)
    t_hi = np.full(caps.e_cap, -1, np.int32)
    t_lo = np.full(caps.e_cap, -1, np.int32)
    t_hi[:edges.shape[0]], t_lo[:edges.shape[0]] = edges[:, 0], edges[:, 1]
    n_q = je._EXPAND_CHUNK
    pick = rng.integers(0, edges.shape[0], n_q // 2)
    rnd = rng.integers(0, graph.n, (n_q - n_q // 2, 2)).astype(np.int32)
    q = np.concatenate([edges[pick], np.sort(rnd, axis=1)])
    q[-7:] = -1                                     # padded queries
    probes = {
        "edge_table": (q, t_hi, t_lo),
        "delete_table": (q, t_hi[:BATCH_OPS], t_lo[:BATCH_OPS]),
    }
    for name, (qq, th, tl) in probes.items():
        args = [jnp.asarray(x) for x in (qq[:, 0], qq[:, 1], th, tl)]
        got, dt = _time(jax.jit(lambda *a: ops.member_probe(*a, backend="pallas")),
                        *args)
        want = ref.member_probe_ref(*args)
        bs, bs_dt = _time(jax.jit(lambda *a: je.edge_probe(*a, use_pallas=False)),
                          *args)
        bad = int(jnp.sum(got != want)) + int(jnp.sum(bs != want))
        print(f"[kernels] member_probe {name}: n_q={qq.shape[0]} n_t={th.shape[0]} "
              f"hits={int(jnp.sum(got))} mismatches={bad} device_s={dt:.6f} "
              f"binary_search_s={bs_dt:.6f}", flush=True)
        _check(bad == 0, f"member_probe {name} disagrees with ref on {bad} queries")
    g, s = caps.group_cap, caps.set_cap
    a = rng.integers(0, 4 * s, (g, s)).astype(np.int32)
    b = rng.integers(0, 4 * s, (g, s)).astype(np.int32)
    a[rng.random((g, s)) < 0.3] = -1
    b[rng.random((g, s)) < 0.3] = -1
    a, b = jnp.asarray(a), jnp.asarray(b)
    got, dt = _time(jax.jit(lambda x, y: ops.set_intersect(x, y, pad=-1,
                                                           backend="pallas")), a, b)
    # The oracle's [G, ca, cb] compare does not fit HBM at once.
    want = jnp.concatenate([ref.set_intersect_ref(a[i:i + 1024], b[i:i + 1024], -1)
                            for i in range(0, g, 1024)])
    bad = int(jnp.sum(got != want))
    print(f"[kernels] set_intersect: G={g} ca={s} cb={s} hits={int(jnp.sum(got))} "
          f"mismatches={bad} device_s={dt:.6f}", flush=True)
    _check(bad == 0, f"set_intersect disagrees with ref on {bad} entries")


def service_phase(log2: int = LOG2, draws: int = DRAWS, batches: int = BATCHES,
                  batch_ops: int = BATCH_OPS, use_pallas=None,
                  patterns=tuple(PATTERNS), tag: str = "service"):
    """Run the sharded service over the update stream; check every
    committed watermark against :func:`reference_counts`."""
    import jax

    from repro.core.pattern import PATTERN_LIBRARY
    from repro.data.graphs import rmat_graph, sample_update
    from repro.stream import BatchScheduler, ListingService

    graph = rmat_graph(log2, draws, seed=SEED)
    print(f"[{tag}] graph R-MAT(2^{log2}, {draws} draws, seed={SEED}): "
          f"n={graph.n} |E|={graph.codes.shape[0]} "
          f"max_deg={int(graph.degrees.max())} devices={jax.device_count()}",
          flush=True)
    half = batch_ops // 2
    edges = {tuple(e) for e in graph.edges().tolist()}
    ref = reference_counts(graph.n, np.array(sorted(edges)))
    t0 = time.perf_counter()
    svc = ListingService(
        graph, backend="sharded", use_pallas=use_pallas,
        caps=service_caps(graph, jax.device_count(), ref["q2_triangle"]),
        max_add=batch_ops, max_del=batch_ops,
        scheduler=BatchScheduler(min_ops=batch_ops, max_ops=batch_ops))
    be = svc.backend
    print(f"[{tag}] m={be.m} caps={be.caps}", flush=True)
    for name in patterns:
        t1 = time.perf_counter()
        be.executor = PATTERNS[name]     # read by the plan compiler at register
        n0 = svc.register(name, PATTERN_LIBRARY[name])
        print(f"[{tag}] register {name}: executor={be.plan(name).executor} "
              f"count={n0} ref={ref[name]} seconds={time.perf_counter() - t1:.3f}",
              flush=True)
        _check(be.plan(name).executor == PATTERNS[name],
               f"{name} planned for {be.plan(name).executor}, not {PATTERNS[name]}")
        _check(n0 == ref[name], f"{name} initial count {n0} != reference {ref[name]}")
    setup_s = time.perf_counter() - t0
    latencies = []
    for b in range(batches):
        upd = sample_update(svc.projected_graph(), half, half, seed=100 + b)
        svc.ingest(upd)
        done = svc.advance()
        _check(len(done) == 1, f"batch {b} committed as {len(done)} micro-batches")
        bm = done[0]
        edges.difference_update(map(tuple, np.asarray(upd.delete).tolist()))
        edges.update(map(tuple, np.asarray(upd.add).tolist()))
        ref = reference_counts(graph.n, np.array(sorted(edges)))
        ref = {k: ref[k] for k in patterns}
        live = svc.counts()
        latencies.append(bm.latency_s)
        print(f"[{tag}] batch {b} watermark={svc.committed_watermark} "
              f"ops={bm.n_ops} (+{bm.net_add}/-{bm.net_delete}) "
              f"latency_s={bm.latency_s:.6f} overflow={bm.overflow} "
              f"storage_overflow={bm.storage_overflow} counts={live} ref={ref}",
              flush=True)
        _check(bm.overflow == 0 and bm.storage_overflow == 0,
               f"batch {b} overflowed device caps")
        _check(live == ref, f"batch {b}: live counts {live} != reference {ref}")
    _check(be.cap_fallbacks == 0 and be.store_resizes == 0,
           f"cap_fallbacks={be.cap_fallbacks} store_resizes={be.store_resizes}")
    prof = svc.obs.jaxprof.steps
    compiles = sum(r.compiles for r in prof.values())
    compile_s = sum(r.compile_seconds for r in prof.values())
    for name, r in sorted(prof.items()):
        print(f"[{tag}] step {name}: compiles={r.compiles} "
              f"compile_s={r.compile_seconds:.3f} calls={r.calls} "
              f"execute_s={r.execute_seconds:.6f}", flush=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", -1)
               for d in jax.devices())
    # Batch 0 also compiles the storage step and the megastep.
    lat = np.array(latencies[1:] or latencies)
    print(f"[{tag}] summary: setup_s={setup_s:.3f} compiles={compiles} "
          f"compile_s={compile_s:.3f} batch0_s={latencies[0]:.6f} "
          f"steady_batch_s median={np.median(lat):.6f} max={lat.max():.6f} "
          f"n={lat.size} peak_hbm_bytes={peak}", flush=True)
    return {"compile_s": compile_s, "compiles": compiles,
            "median_batch_s": float(np.median(lat)), "peak_hbm_bytes": peak}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the multi-device service phase")
    ap.add_argument("--patterns", default=",".join(PATTERNS),
                    help="comma-separated subset of " + ",".join(PATTERNS))
    ap.add_argument("--use-pallas", choices=("on", "off"), default=None,
                    help="force the service's Pallas edge probes on or off "
                         "(default: the platform default)")
    args = ap.parse_args(argv)
    patterns = tuple(args.patterns.split(","))
    if not set(patterns) <= set(PATTERNS):
        ap.error(f"--patterns must be a subset of {','.join(PATTERNS)}")
    try:
        import repro

        print(f"[device] compile cache: {repro.use_compile_cache()}", flush=True)
        info = device_phase(args.chips)
        if args.chips == 1:
            from repro.data.graphs import rmat_graph

            g = rmat_graph(LOG2, DRAWS, seed=SEED)
            tri = reference_counts(g.n, g.edges())["q2_triangle"]
            kernel_phase(g, service_caps(g, 1, tri))
        service_phase(patterns=patterns,
                      use_pallas=None if args.use_pallas is None
                      else args.use_pallas == "on",
                      tag="service" if args.chips == 1 else "service4")
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
