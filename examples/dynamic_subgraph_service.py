"""End-to-end driver of ``repro.stream``: a continuous listing service.

The paper's deployment story, productionized: several patterns stay live
over one update stream (§VII-C protocol — batches of half deletions /
half insertions). Updates are ingested into the journal, the scheduler
nets them into cost-model-sized micro-batches, one shared delta drives
every pattern (Alg. 4 once per batch), sinks stream count deltas out,
and a from-scratch audit re-lists one pattern every ``--audit-every``
batches.

``--backend sharded`` is **device-resident**: each pattern's running
match set lives on the mesh as a sharded ``MatchStore`` and every batch
runs one fused maintain step (patch ∘ filter ∘ merge ∘ count) per
pattern on device. With only the count sink subscribed, batches move
scalars device→host — the ``hostB`` field of the per-batch line stays 0
(add a match-delta sink and it jumps: rows materialize lazily, on
demand).

``--obs-dir DIR`` turns on full observability (span tracing included)
and exports the whole bundle on exit: metrics JSON + Prometheus text,
the span tree as JSONL + Chrome trace-event JSON (open in
https://ui.perfetto.dev), and the per-step compile/execute profile on
the sharded backend.

``--reoptimize`` attaches a :class:`repro.stream.PlanManager`: every
committed batch it watches the scheduler's drift EWMA and periodically
recompiles each pattern's join tree from live stats through the staged
plan compiler (``repro.planner``), hot-swapping a plan at the watermark
when the Eq. 11 re-cost says the incumbent has gone stale. Swap
decisions are printed at the end; with ``--obs-dir`` the compiled-plan
dumps and ``plan_swap`` spans land in the export bundle.

    PYTHONPATH=src python examples/dynamic_subgraph_service.py --batches 8
    PYTHONPATH=src python examples/dynamic_subgraph_service.py --backend sharded
    PYTHONPATH=src python examples/dynamic_subgraph_service.py --obs-dir /tmp/obs
    PYTHONPATH=src python examples/dynamic_subgraph_service.py --reoptimize
"""

import argparse

import repro
from repro.core.pattern import PATTERN_LIBRARY
from repro.data.graphs import rmat_graph, sample_update
from repro.stream import (
    BatchScheduler,
    CountDeltaSink,
    ListingService,
    Observability,
    PlanManager,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=8, help="ingest rounds")
    ap.add_argument("--batch-size", type=int, default=50)
    ap.add_argument("--patterns", default="q2_triangle,q1_square,q5_house")
    ap.add_argument("--audit-every", type=int, default=4)
    ap.add_argument("--m", type=int, default=4)
    ap.add_argument("--backend", choices=("host", "sharded"), default="host")
    ap.add_argument("--executor", choices=("auto", "tree", "wcoj"),
                    default="tree",
                    help="join executor mode: 'tree' (VCBC join trees), "
                         "'wcoj' (force the worst-case-optimal generic "
                         "join; dense patterns only), or 'auto' (compiler "
                         "picks per pattern from the cost model)")
    ap.add_argument("--target-cost", type=float, default=250_000.0,
                    help="scheduler per-micro-batch work budget (cost units)")
    ap.add_argument("--obs-dir", default=None,
                    help="enable span tracing and export the observability "
                         "bundle (metrics snapshot, Prometheus text, Chrome "
                         "trace, compiled-plan dumps, device-step profile) "
                         "into this directory")
    ap.add_argument("--reoptimize", action="store_true",
                    help="drift-triggered online join-tree re-optimization: "
                         "recompile plans from live stats and hot-swap at "
                         "committed watermarks")
    ap.add_argument("--drift-threshold", type=float, default=1.5,
                    help="scheduler drift EWMA that triggers a recompile")
    ap.add_argument("--recost-every", type=int, default=16,
                    help="also recompile every K batches (0 disables)")
    args = ap.parse_args()
    repro.use_compile_cache()

    pm = PlanManager(drift_threshold=args.drift_threshold,
                     recost_every=args.recost_every) if args.reoptimize else None

    if args.backend == "sharded":
        graph = rmat_graph(6, 400, seed=0)     # sharded demo: device-sized
        kw = dict(max_add=args.batch_size, max_del=args.batch_size)
    else:
        graph = rmat_graph(10, 5000, seed=0)
        kw = dict(m=args.m)
    svc = ListingService(
        graph, backend=args.backend, audit_every=args.audit_every,
        scheduler=BatchScheduler(target_cost=args.target_cost,
                                 max_ops=args.batch_size),
        obs=Observability.full() if args.obs_dir else None,
        plan_manager=pm, executor=args.executor, **kw)
    counts = svc.subscribe(CountDeltaSink())

    for name in args.patterns.split(","):
        n0 = svc.register(name, PATTERN_LIBRARY[name])
        meta = svc.backend.meta(name)
        mode = meta.plan.executor if meta.plan is not None else "tree"
        print(f"[init] {name}: |M|={n0} executor={mode}")

    seen_audits = 0
    for b in range(args.batches):
        upd = sample_update(svc.projected_graph(), args.batch_size // 2,
                            args.batch_size // 2, seed=100 + b)
        svc.ingest(upd)
        for bm in svc.advance():
            per = " ".join(
                f"{n}:|M|={r.count_after}(+{r.patch_groups}g)"
                for n, r in bm.patterns.items())
            cand = (f" cand={bm.cand_vertices}v/{bm.cand_edges}e"
                    if bm.cand_vertices >= 0 else "")
            host_b = (f" hostB={bm.host_bytes}"
                      if args.backend == "sharded" else "")
            cache = (f" cache={bm.cache_hits}h/{bm.cache_misses}m"
                     f"/{bm.invalidated_parts}inv"
                     if bm.cache_hits >= 0 else "")
            print(f"[batch {bm.batch_index}] ops={bm.n_ops} "
                  f"(net +{bm.net_add}/-{bm.net_delete}) "
                  f"{bm.latency_s*1e3:.0f}ms {bm.throughput_ops_s:.0f}op/s "
                  f"ovf={bm.overflow}{cand}{host_b}{cache} {per}")
        for bi, name, ok in svc.audits[seen_audits:]:
            print(f"[audit] batch {bi} {name}: {'OK' if ok else 'MISMATCH'}")
        seen_audits = len(svc.audits)

    print(f"service run complete: counts={svc.counts()} "
          f"watermark={svc.committed_watermark} "
          f"journal_compacted={svc.compact()} entries")
    print(f"count deltas seen by sink: {counts.totals}")
    drift = svc.scheduler.drift()
    if drift is not None:
        print(f"scheduler drift (observed/predicted EWMA): {drift:.2f}")
    if pm is not None:
        for ev in pm.events:
            verdict = ("SWAPPED" if ev.swapped else "kept")
            print(f"[replan] batch {ev.batch_index} {ev.pattern} "
                  f"({ev.trigger}, drift={ev.drift and f'{ev.drift:.2f}'}): "
                  f"inc={ev.incumbent_cost:.3g} cand={ev.candidate_cost:.3g} "
                  f"-> {verdict}"
                  + (f" |M|={ev.count} in {ev.elapsed_s*1e3:.0f}ms"
                     if ev.swapped else ""))
    if args.obs_dir:
        for kind, path in sorted(svc.obs.export(args.obs_dir).items()):
            print(f"[obs] {kind}: {path}")


if __name__ == "__main__":
    main()
