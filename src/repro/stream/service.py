"""`ListingService` — continuous multi-pattern subgraph listing.

The streaming composition of the paper's two stages::

    ingest()  →  UpdateJournal  →  BatchScheduler  →  SharedDelta
                                                        │ once per batch
                      ┌─────────────────────────────────┤
                      ▼                                 ▼
               HostBackend                       ShardedBackend
         (NumPy Alg. 4 + Nav-join;       (device make_storage_update_step
          shared Φ(d') + seed cache +     once + ONE fused multi-pattern
          delta-maintained                maintain megastep over every
          PartitionUnitCache)             device-resident MatchStore +
                      │                   per-device unit-table carries)
                      └────────────── sinks ────────────┘
                           (count deltas, match deltas)

Both backends obey the same contract (:class:`StreamBackend`): register
patterns, apply one shared delta to all of them, report per-pattern
results, and :meth:`~StreamBackend.materialize` full match tables only
on demand — the sharded backend keeps running match sets on the mesh
end to end and byte-accounts every device→host pull
(``BatchMetrics.host_bytes``). The service owns the journal, the
committed watermark, batch metrics, periodic from-scratch audits, and
sink fan-out.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.ddsl import DDSL
from repro.core.estimator import GraphStats
from repro.core.graph import Graph, GraphUpdate, decode_edges, edge_codes
from repro.core.incremental import removed_rows
from repro.core.pattern import Pattern, R1Unit
from repro.core.storage import build_np_storage
from repro.core.vcbc import CompressedTable, Ragged, compress_table
from repro.planner import CompileContext, CompiledPlan, compile_plan
from repro.planner.sizing import quantize_store_caps

from repro.obs import Observability, ProfiledStep

from .journal import UpdateJournal
from .scheduler import (
    BatchScheduler,
    SharedDelta,
    compute_shared_delta,
    probe_inc,
)
from .sinks import BatchEvent, Sink

__all__ = [
    "PatternMeta",
    "PatternReport",
    "BatchMetrics",
    "StreamBackend",
    "HostBackend",
    "ShardedBackend",
    "ListingService",
]


@dataclasses.dataclass(frozen=True)
class PatternMeta:
    """Static per-pattern facts shared by backends, scheduler, audits.

    ``cover``/``ord_``/``units`` are views into ``plan`` (kept flat
    because every consumer reads them); the full
    :class:`~repro.planner.CompiledPlan` — tree, IR program, caps,
    per-pass report — rides along for the obs export and plan swaps.
    """

    name: str
    pattern: Pattern
    cover: Tuple[int, ...]
    ord_: Tuple[Tuple[int, int], ...]
    units: Tuple[R1Unit, ...]
    plan: Optional[CompiledPlan] = None


@dataclasses.dataclass
class PatternReport:
    """One pattern's outcome for one committed micro-batch."""

    name: str
    count_before: int
    count_after: int
    latency_s: float
    patch_groups: int = 0
    removed_groups: int = 0
    overflow: int = 0
    added: Optional[np.ndarray] = None
    removed: Optional[np.ndarray] = None


@dataclasses.dataclass
class BatchMetrics:
    """Service-level record of one committed micro-batch."""

    batch_index: int
    lo: int
    hi: int
    n_ops: int
    net_add: int
    net_delete: int
    latency_s: float
    patterns: Dict[str, PatternReport]
    storage_overflow: int = 0   # device storage-step overflow (once per batch)
    # Candidate-set sizes of the delta-restricted device update (C1–C3);
    # -1 where not applicable (host backend / full-gather mode). Reset
    # every micro-batch — these are per-batch sizes, not running totals.
    cand_vertices: int = -1
    cand_edges: int = -1
    # Bytes of match/patch state pulled device→host while applying this
    # batch (sharded backend; always 0 on the host backend). Count-only
    # batches keep the running match sets on the mesh, so this is 0
    # unless a sink demanded decompressed rows — asserted in tests.
    host_bytes: int = 0
    # Delta-maintained unit-table cache traffic of this batch: tables
    # served from cache vs re-listed, and partitions the netted delta
    # invalidated. On a warm stream cache_misses is bounded by
    # |units| · invalidated_parts — the §IV-D `fixed` term scales with
    # the delta, not the graph. -1 where the backend has no cache.
    cache_hits: int = -1
    cache_misses: int = -1
    invalidated_parts: int = -1
    # §IV-D scheduler prediction for this batch (seconds); -1 until the
    # cost-unit → wall-clock scale is calibrated (first batches). The
    # drift EWMA over observed/predicted is the scheduler gauge.
    predicted_s: float = -1.0

    @property
    def throughput_ops_s(self) -> float:
        # Batches finishing below clock resolution have no measurable
        # rate: report 0.0, never inf (they are likewise excluded from
        # the throughput gauge — dashboards must not render infinities).
        return self.n_ops / self.latency_s if self.latency_s > 0 else 0.0

    @property
    def overflow(self) -> int:
        return self.storage_overflow + sum(r.overflow for r in self.patterns.values())


def _save_table(path: str, table: CompressedTable) -> None:
    """One pattern's compressed match set as an ``.npz`` (snapshot half;
    the pattern itself travels in the snapshot's ``meta.json``)."""
    arrs = {
        "skeleton": np.asarray(table.skeleton, np.int64),
        "skeleton_cols": np.asarray(table.skeleton_cols, np.int64),
        "cover": np.asarray(table.cover, np.int64),
        "comp_labels": np.asarray(sorted(table.comp), np.int64),
    }
    for v, r in table.comp.items():
        arrs[f"offsets_{int(v)}"] = np.asarray(r.offsets, np.int64)
        arrs[f"values_{int(v)}"] = np.asarray(r.values, np.int64)
    np.savez(path, **arrs)


def _load_table(path: str, pattern: Pattern) -> CompressedTable:
    z = np.load(path)
    comp = {int(v): Ragged(offsets=z[f"offsets_{int(v)}"],
                           values=z[f"values_{int(v)}"])
            for v in z["comp_labels"]}
    return CompressedTable(
        pattern=pattern,
        cover=tuple(int(c) for c in z["cover"]),
        skeleton_cols=tuple(int(c) for c in z["skeleton_cols"]),
        skeleton=z["skeleton"], comp=comp,
    )


def _meta_from_plan(name: str, plan: CompiledPlan) -> PatternMeta:
    return PatternMeta(name=name, pattern=plan.pattern, cover=plan.cover,
                       ord_=plan.ord, units=plan.units, plan=plan)


class StreamBackend:
    """Interface both execution backends implement (duck-typed)."""

    #: scheduler batch ceiling imposed by static shapes (None = unbounded)
    max_batch_ops: Optional[int] = None
    #: the owning service's observability object. The service assigns it
    #: in ``__init__`` (before any pattern registers); a backend driven
    #: standalone lazily grows its own default (registry on, tracing
    #: off) so instrumentation never needs None guards.
    obs: Optional[Observability] = None
    #: overflow of the last batch's shared (pattern-independent) storage
    #: update — reported once per batch, not per pattern
    last_storage_overflow: int = 0
    #: device→host bytes of the last batch / of the backend's lifetime.
    #: Host backends never move anything (0); sharded backends account
    #: every match-set / patch materialization here.
    last_host_bytes: int = 0
    total_host_bytes: int = 0
    #: unit-table cache traffic of the last batch (-1 = no cache)
    last_cache_hits: int = -1
    last_cache_misses: int = -1
    last_invalidated_parts: int = -1

    def _obs(self) -> Observability:
        o = self.obs
        if o is None:
            o = self.obs = Observability()
        return o

    def _jaxprof(self):
        """Late-bound profiler resolver for :class:`ProfiledStep` — the
        service attaches ``obs`` after backend construction, so wrapped
        steps must look it up at call time."""
        o = self.obs
        return o.jaxprof if o is not None else None

    def register(self, name: str, pattern: Pattern, cover=None) -> int:
        raise NotImplementedError

    def compile(self, pattern: Pattern, cover=None,
                stats: GraphStats | None = None,
                objective: str = "r_lower") -> CompiledPlan:
        """Run the staged plan compiler against this backend's machine
        shape (mesh width, engine caps, store headroom). The **single
        entry point** for plan construction: register, restore, and the
        plan manager's live recompiles all come through here, so no two
        paths can ever pick different trees from the same stats.
        ``objective`` is the free-cover policy (§IV-F ``"r_lower"``
        storage argmax, or ``"cost"`` — the Eq. 11 runtime argmin the
        online re-optimizer uses)."""
        raise NotImplementedError

    def plan(self, name: str) -> Optional[CompiledPlan]:
        """The compiled plan the pattern is currently executing."""
        return self.meta(name).plan

    def remove_pattern(self, name: str) -> None:
        """Forget a pattern (engine/device state and counts). The swap
        half-step between :meth:`materialize` and :meth:`install_plan`;
        the caller owns scheduler bookkeeping."""
        raise NotImplementedError

    def install_plan(self, name: str, plan: CompiledPlan, table) -> int:
        """Install a precompiled plan with a known match set at the
        committed watermark (``table.cover`` must equal ``plan.cover``)
        — :meth:`restore_pattern` with the compile step factored out, so
        a plan swap can install the exact plan it costed."""
        raise NotImplementedError

    def apply_batch(self, delta: SharedDelta, want_matches) -> Dict[str, PatternReport]:
        raise NotImplementedError

    def materialize(self, name: str):
        """The pattern's current match set as a host
        :class:`~repro.core.vcbc.CompressedTable` — the **on-demand**
        half of the contract. Backends keeping results device-resident
        pull (and byte-account) them only when this is called; sinks
        that set ``wants_matches`` and from-scratch parity checks are
        the intended triggers."""
        raise NotImplementedError

    def restore_pattern(self, name: str, pattern: Pattern,
                        cover: Tuple[int, ...], table) -> int:
        """Register a pattern whose match set is already known (a
        snapshot table at the service's committed watermark) — skips the
        from-scratch initial listing."""
        raise NotImplementedError

    def _noop_reports(self) -> Dict[str, PatternReport]:
        """Per-pattern reports for a window that netted to the empty
        update: counts unchanged, no deltas, no device/engine work."""
        return {name: PatternReport(
            name=name, count_before=self.count(name),
            count_after=self.count(name), latency_s=0.0,
        ) for name in self.names()}

    def meta(self, name: str) -> PatternMeta:
        raise NotImplementedError

    def count(self, name: str) -> int:
        raise NotImplementedError

    def names(self) -> List[str]:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Host backend: NumPy engines over one shared NP storage
# ---------------------------------------------------------------------------

class HostBackend(StreamBackend):
    """All patterns share one Φ(d); Alg. 4 runs once per batch.

    One :class:`~repro.core.unit_cache.PartitionUnitCache` fronts every
    per-partition unit listing of every registered pattern: Nav-join
    chain steps and seed derivations pull through it, and each batch
    invalidates exactly the partitions its Alg. 4 update dirtied
    (``UpdateCostReport.dirty_parts``) — the §IV-D `fixed` term becomes
    delta-bounded. Cached and uncached paths byte-match at every
    watermark (property-tested).
    """

    kind = "host"

    def __init__(self, graph: Graph, m: int = 4, h=None,
                 cache_max_entries: Optional[int] = None,
                 cache_max_bytes: Optional[int] = None,
                 executor: str = "tree"):
        from repro.core.unit_cache import PartitionUnitCache

        self.executor = executor
        self.storage = build_np_storage(graph, m, h)
        self.unit_cache = PartitionUnitCache(
            self.storage, max_entries=cache_max_entries,
            max_bytes=cache_max_bytes)
        self.engines: Dict[str, DDSL] = {}
        self._meta: Dict[str, PatternMeta] = {}
        self._counts: Dict[str, int] = {}   # carried across batches

    @property
    def m(self) -> int:
        return self.storage.m

    @property
    def graph(self) -> Graph:
        return self.storage.graph

    def compile(self, pattern: Pattern, cover=None,
                stats: GraphStats | None = None,
                objective: str = "r_lower") -> CompiledPlan:
        return compile_plan(CompileContext(
            pattern=pattern,
            stats=stats if stats is not None else GraphStats.of(self.graph),
            m=self.m,
            cover=tuple(sorted(int(c) for c in cover)) if cover is not None else None,
            cover_objective=objective,
            executor=self.executor,
        ))

    def register(self, name: str, pattern: Pattern, cover=None) -> int:
        if name in self.engines:
            raise ValueError(f"pattern {name!r} already registered")
        meta = _meta_from_plan(name, self.compile(pattern, cover))
        eng = DDSL(self.graph, pattern, m=self.m, storage=self.storage,
                   plan=meta.plan)
        eng.initial()
        self.engines[name] = eng
        self._meta[name] = meta
        self._counts[name] = eng.count()
        return self._counts[name]

    def restore_pattern(self, name: str, pattern: Pattern,
                        cover: Tuple[int, ...], table) -> int:
        return self.install_plan(name, self.compile(pattern, cover), table)

    def install_plan(self, name: str, plan: CompiledPlan, table) -> int:
        if name in self.engines:
            raise ValueError(f"pattern {name!r} already registered")
        if table.cover != plan.storage_cover:
            # Snapshot from a different cover or executor mode (WCOJ
            # stores under trivial compression) — recompress to the
            # plan's storage layout.
            cols, rows = table.decompress(plan.ord)
            table = compress_table(plan.pattern, plan.storage_cover, cols, rows)
        meta = _meta_from_plan(name, plan)
        eng = DDSL(self.graph, plan.pattern, m=self.m, storage=self.storage,
                   plan=plan)
        eng.state.matches = table          # the known table replaces initial()
        self.engines[name] = eng
        self._meta[name] = meta
        self._counts[name] = eng.count()
        return self._counts[name]

    def remove_pattern(self, name: str) -> None:
        del self.engines[name]
        del self._meta[name]
        del self._counts[name]

    def meta(self, name: str) -> PatternMeta:
        return self._meta[name]

    def names(self) -> List[str]:
        return list(self.engines)

    def count(self, name: str) -> int:
        return self._counts[name]

    def materialize(self, name: str):
        return self.engines[name].state.matches

    def matches_plain(self, name: str) -> np.ndarray:
        return self.engines[name].matches_plain()

    def apply_batch(self, delta: SharedDelta, want_matches) -> Dict[str, PatternReport]:
        obs = self._obs()
        tr = obs.tracer
        self.last_cache_hits = 0
        self.last_cache_misses = 0
        self.last_invalidated_parts = 0
        if delta.update.size == 0:
            # The window netted to nothing: Φ, stats, and every match
            # set are unchanged — commit the watermark without work
            # (the unit cache stays fully warm too).
            return self._noop_reports()
        ev0 = self.unit_cache.stats.evictions
        with tr.span("storage_update") as ssp:
            storage2 = delta.ensure_storage(self.storage)   # Alg. 4 — once
            # Advance the unit-table cache to Φ(d'): exactly the
            # partitions whose stored edge set changed lose their cached
            # listings.
            dirty = (delta.storage_report.dirty_parts
                     if delta.storage_report is not None
                     else tuple(range(self.storage.m)))
            stats0 = self.unit_cache.stats.snapshot()
            self.unit_cache.advance(storage2, dirty)
            ssp.add("dirty_parts", len(dirty))
        reports: Dict[str, PatternReport] = {}
        for name, eng in self.engines.items():
            with tr.span("maintain", pattern=name) as msp:
                t0 = time.perf_counter()
                before = self._counts[name]
                want = name in want_matches
                removed = (removed_rows(eng.state.matches, delta.update.delete, eng.ord_)
                           if want else None)
                rep = eng.apply_shared(
                    storage2, delta.update,
                    stats=delta.stats, storage_report=delta.storage_report,
                    seed_fn=delta.seed_provider(eng.cover, eng.ord_,
                                                cache=self.unit_cache),
                    provider=self.unit_cache,
                )
                added = rep.patch.decompress(eng.ord_)[1] if (want and rep.patch is not None) else None
                self._counts[name] = eng.count()
                patch_groups = rep.patch.n_groups if rep.patch is not None else 0
                msp.add("patch_groups", patch_groups)
                msp.add("removed_groups", rep.removed_groups)
                reports[name] = PatternReport(
                    name=name, count_before=before, count_after=self._counts[name],
                    latency_s=time.perf_counter() - t0,
                    patch_groups=patch_groups,
                    removed_groups=rep.removed_groups,
                    added=added, removed=removed,
                )
        self.storage = storage2
        hits, misses, inval = (b - a for a, b in
                               zip(stats0, self.unit_cache.stats.snapshot()))
        self.last_cache_hits = hits
        self.last_cache_misses = misses
        self.last_invalidated_parts = inval
        probe_inc("cache_hits", hits, metrics=obs.metrics)
        probe_inc("cache_misses", misses, metrics=obs.metrics)
        probe_inc("invalidated_parts", inval, metrics=obs.metrics)
        evictions = self.unit_cache.stats.evictions - ev0
        if evictions:
            obs.metrics.counter(
                "unit_cache_evictions_total",
                "unit-cache LRU evictions under the entry/byte budget",
            ).inc(evictions)
        obs.metrics.gauge(
            "unit_cache_resident_bytes",
            "bytes held by cached unit tables (plain + compressed)",
        ).set(self.unit_cache.resident_bytes)
        obs.metrics.gauge(
            "unit_cache_entries", "live plain unit-cache entries",
        ).set(self.unit_cache.entries())
        return reports


# ---------------------------------------------------------------------------
# Sharded backend: device storage step once + per-pattern patch steps
# ---------------------------------------------------------------------------

def _default_caps(storage, graph: Graph, m: int):
    """Size EngineCaps from the built storage with growth headroom."""
    from repro.dist import jax_engine as je

    nv = max(max((int(p.vertices.shape[0]) for p in storage.parts), default=1), graph.n // m + 1)
    ne = max((int(p.codes.shape[0]) for p in storage.parts), default=1)
    dg = max((int(np.diff(p.indptr).max(initial=0)) for p in storage.parts), default=1)

    def up(x, mult, align):
        return int(-(-max(1, int(x * mult)) // align) * align)

    v_cap = up(max(nv, graph.n / m), 1.5, 64)
    return je.EngineCaps(
        v_cap=v_cap, deg_cap=up(dg, 2.0, 8), e_cap=up(ne, 2.0, 64),
        match_cap=4096, group_cap=4096, set_cap=64, pair_cap=128,
    )


@dataclasses.dataclass
class _ShardedEntry:
    meta: PatternMeta
    prog: object
    full_skel: Tuple[int, ...]
    store: object                   # device-resident MatchStore
    store_caps: object
    unit_caps: object               # StoreCaps of the unit-table carry
    carry: object                   # persistent per-device unit tables
    n_unit_plans: int               # distinct unit plans behind the carry
    refresh_step: object            # cold carry refresh (also crash recovery)
    list_step: object = None        # lazy initial-calculation step (rebuilds)
    host_table: object = None       # lazy comp_to_host cache (per watermark)
    wcoj_level_caps: object = None  # calibrated per-level caps (wcoj mode)


class ShardedBackend(StreamBackend):
    """Drives the ``repro.dist`` SPMD steps behind the backend contract.

    One jitted :func:`~repro.dist.sharded.make_storage_update_step`
    (pattern-independent) advances Φ(d') on device once per batch;
    *every* registered pattern is then maintained by ONE jitted
    :func:`~repro.dist.sharded.make_maintain_mega_step` — per pattern,
    carry refresh ∘ patch ∘ delete filter ∘ merge ∘ count over its
    device-resident :class:`~repro.dist.sharded.MatchStore`, all fused
    into a single SPMD dispatch that shares the updated partitions and
    the delete-table dedup across patterns. Running match sets never
    leave the mesh: a count-only batch pulls scalars, and full tables
    materialize on host only through :meth:`materialize` (lazy, valid
    prefix only, byte-accounted in ``last_host_bytes``). Each pattern
    also carries its per-device **unit tables** (the Nav-join `fixed`
    cost): the megastep re-lists them only on devices whose partition
    the storage step's ``part_dirty`` flag marks, so a warm batch's
    listing work is delta-bounded.

    The megastep donates the store and carry buffers on platforms where
    XLA honors donation (:func:`repro.dist.sharded.donate_jit`), keeping
    per-batch device memory flat. The backend therefore treats the
    passed-in stores/carries as consumed: every retry/abort path
    rebuilds them from the never-donated committed partitions
    (``self.pt``), so a failed batch always leaves a usable backend at
    the committed watermark.

    Device cap overflow is surfaced per batch in the reports — never
    silent. A *store* overflow (a running match set outgrowing its
    ``StoreCaps``) is self-healing by default: nothing commits, the
    overflowing patterns' caps double (on the pow2 grid of
    :func:`~repro.planner.sizing.quantize_store_caps`), the stores are
    rebuilt by re-listing over Φ, the megastep recompiles (counted
    under the same ``maintain_mega`` profile) and the batch is retried
    (counted in ``store_resizes``, like ``cap_fallbacks``).
    ``strict_overflow=True`` opts back into fail-stop semantics: any
    storage/maintain overflow raises before committing lossy state
    (capped device state is persistent — a dropped candidate or store
    group stays wrong forever).
    """

    kind = "sharded"

    #: candidate-set sizes of the last batch's storage step (delta mode;
    #: -1 in full-gather mode). Reset at the top of every apply_batch.
    last_cand_vertices: int = -1
    last_cand_edges: int = -1
    #: times the estimator-sized candidate caps were outrun and the
    #: backend permanently fell back to the never-overflow derivation
    #: (recompiling the storage step and retrying the batch).
    cap_fallbacks: int = 0
    #: times a MatchStore outgrew its caps and was rebuilt with ×2 caps
    #: (best-effort mode; counted, like cap_fallbacks).
    store_resizes: int = 0
    _max_store_resizes: int = 4

    def __init__(self, graph: Graph, m: int | None = None, caps=None,
                 max_add: int = 64, max_del: int = 64,
                 use_pallas: bool | None = None,
                 update_mode: str = "delta", cap_sizing: str = "estimator",
                 store_headroom: float = 4.0, strict_overflow: bool = False,
                 executor: str = "tree", level_headroom: float = 1.5):
        import jax
        from jax.sharding import NamedSharding

        from repro.dist import jax_engine as je   # noqa: F401  (caps type)
        from repro.dist import sharded

        self._sharded = sharded
        self._je = je
        self.executor = executor
        self.m = jax.local_device_count() if m is None else int(m)
        self.mesh = jax.make_mesh((self.m,), ("data",))
        storage = build_np_storage(graph, self.m)
        self.caps = caps if caps is not None else _default_caps(storage, graph, self.m)
        if use_pallas is not None:   # None keeps the EngineCaps default
            self.caps = dataclasses.replace(self.caps, use_pallas=use_pallas)
        self.max_batch_ops = min(max_add, max_del)
        self._max_add, self._max_del = max_add, max_del
        if cap_sizing == "estimator":
            # §IV-D-sized candidate caps (clamped to the never-overflow
            # bound, so this only ever shrinks the psum payload). If a
            # batch does outrun them — a hub-concentrated delta — the
            # step reports overflow BEFORE anything commits and
            # apply_batch falls back to the never-overflow caps
            # permanently (one recompile) and retries the same batch.
            self.ushapes = sharded.UpdateShapes.from_estimator(
                max_add, max_del, GraphStats.of(graph), self.caps, self.m)
        elif cap_sizing == "exact":
            self.ushapes = sharded.UpdateShapes(n_add=max_add, n_del=max_del)
        else:
            raise ValueError(
                f"unknown cap_sizing {cap_sizing!r} (expected 'estimator' or 'exact')")
        self.graph = graph
        if graph.n > self.m * self.caps.v_cap:
            raise ValueError(
                f"graph has {graph.n} vertices > m*v_cap={self.m * self.caps.v_cap}")
        self.update_mode = update_mode
        self.store_headroom = float(store_headroom)
        # Per-level WCOJ listing caps are transient (rebuilt every
        # dispatch, overflow detected before anything commits), so they
        # can hug the observed prefix sizes much tighter than the
        # persistent store caps — the pow2 grid alone already adds
        # slack. This gap is most of the executor's win: each level
        # pays its own prefix size, not a uniform worst-case cap.
        self.level_headroom = float(level_headroom)
        # Device caps make persistent state lossy when exceeded: a
        # dropped candidate vertex corrupts Φ(d') forever, a dropped
        # store group loses matches that no later patch re-derives.
        # Best-effort mode (default) self-heals store overflow by
        # rebuilding with ×2 caps and retrying the batch before
        # anything commits; other overflow stays a counted metric
        # (watch BatchMetrics.overflow). Strict mode raises instead of
        # carrying any potentially corrupted state forward — opt in for
        # fail-stop deployments.
        self.strict_overflow = bool(strict_overflow)
        #: the fused multi-pattern maintain megastep (None until the
        #: first pattern registers) and its per-pattern cost shares
        self.maintain_step: Optional[ProfiledStep] = None
        self._maintain_subs: Dict[str, float] = {}
        # Every jitted SPMD step is wrapped in a ProfiledStep so the
        # device profiler can split compile from execute per step name.
        # The profiler resolves late (self._jaxprof) — the service
        # attaches `obs` after this constructor runs.
        self.storage_step = ProfiledStep(
            "storage_update",
            sharded.make_storage_update_step(
                self.mesh, self.caps, self.ushapes, mode=update_mode),
            self._jaxprof)
        specs = sharded.partition_specs(self.mesh)
        self._shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)
        self.pt = jax.device_put(
            sharded.stack_partitions(storage, self.caps), self._shardings)
        self.entries: Dict[str, _ShardedEntry] = {}
        self._counts: Dict[str, int] = {}   # carried across batches
        #: entries removed since the last batch, kept for carry reuse on
        #: a same-watermark plan swap (cleared whenever Φ advances)
        self._carry_stash: Dict[str, _ShardedEntry] = {}
        self.last_host_bytes = 0
        self.total_host_bytes = 0

    def _pull(self, arr) -> np.ndarray:
        """Device→host transfer with byte accounting."""
        a = np.asarray(arr)
        self.last_host_bytes += int(a.nbytes)
        self.total_host_bytes += int(a.nbytes)
        self._obs().metrics.counter(
            "host_transfer_bytes_total",
            "device→host bytes pulled through the sharded backend",
        ).inc(int(a.nbytes))
        return a

    def _flatten(self, tc):
        """Pull stacked [M, G, ...] compressed tensors to host form."""
        skel = self._pull(tc.skeleton).reshape(-1, tc.skeleton.shape[-1])
        valid = self._pull(tc.valid).reshape(-1)
        sets = {k: self._pull(v).reshape(-1, v.shape[-1])
                for k, v in tc.sets.items()}
        return self._je.CompTensors(skeleton=skel, valid=valid, sets=sets)

    def compile(self, pattern: Pattern, cover=None,
                stats: GraphStats | None = None,
                objective: str = "r_lower") -> CompiledPlan:
        return compile_plan(CompileContext(
            pattern=pattern,
            stats=stats if stats is not None else GraphStats.of(self.graph),
            m=self.m, caps=self.caps,
            cover=tuple(sorted(int(c) for c in cover)) if cover is not None else None,
            cover_objective=objective,
            store_headroom=self.store_headroom,
            executor=self.executor,
        ))

    def register(self, name: str, pattern: Pattern, cover=None) -> int:
        if name in self.entries:
            raise ValueError(f"pattern {name!r} already registered")
        meta = _meta_from_plan(name, self.compile(pattern, cover))
        if meta.plan.executor == "wcoj":
            return self._register_wcoj(name, meta)
        prog = meta.plan.program
        list_step = ProfiledStep(
            f"list:{name}",
            self._sharded.make_list_step(prog, self.mesh, self.caps),
            self._jaxprof)
        out, diag = list_step(self.pt)
        if int(diag["overflow"]):
            raise ValueError(
                f"initial listing overflowed caps ({int(diag['overflow'])} rows); "
                "re-register with larger EngineCaps")
        # The initial match set goes straight into a device-resident
        # store (sharded by full-skeleton ownership) and is counted on
        # device — registration never materializes matches on host.
        # Caps live on the quantize_store_caps pow2 grid so patterns
        # with near-identical estimates share megastep shapes.
        store_caps = quantize_store_caps(meta.plan.store_caps)
        init_step = ProfiledStep(
            f"init_store:{name}",
            self._sharded.make_init_store_step(
                prog, self.mesh, self.caps, store_caps),
            self._jaxprof)
        store, idiag = init_step(out)
        if int(idiag["overflow"]):
            raise ValueError(
                f"initial match store overflowed caps ({int(idiag['overflow'])} "
                "entries); re-register with a larger store_headroom")
        entry = self._make_entry(name, meta, store, store_caps,
                                 list_step=list_step)
        self._counts[name] = int(idiag["count"])
        return self._counts[name]

    def _register_wcoj(self, name: str, meta: PatternMeta) -> int:
        """Register under the generic-join executor mode: anchored WCOJ
        listing → trivially-compressed device store. No unit-table carry
        — the per-batch patch is a delta-seeded re-run of the same
        generic join, not a Nav-join over cached unit tables."""
        plan = meta.plan
        level_caps, store_floor = self._calibrate_wcoj_caps(plan)
        list_step = ProfiledStep(
            f"list:{name}",
            self._sharded.make_wcoj_list_step(
                plan.pattern, plan.wcoj, self.mesh, self.caps, level_caps),
            self._jaxprof)
        out, diag = list_step(self.pt)
        if int(diag["overflow"]):
            raise ValueError(
                f"initial WCOJ listing overflowed level caps "
                f"({int(diag['overflow'])} rows); re-register with a larger "
                "store_headroom")
        # Store groups are whole matches under trivial compression, so
        # the calibrated bound (observed per-partition match count ×
        # store_headroom) is the group sizing, floored at the engine
        # group cap. The estimator's final-level bound is not a floor:
        # it overshoots dense patterns by orders of magnitude (4-cliques
        # of the UK~ R-MAT graph: 33.5M groups against 696k matches),
        # and every batch filters, sorts and merges the whole store.
        store_caps = quantize_store_caps(dataclasses.replace(
            plan.store_caps,
            group_cap=max(self.caps.group_cap, store_floor)))
        init_step = ProfiledStep(
            f"init_store:{name}",
            self._sharded.make_wcoj_init_store_step(
                plan.pattern, plan.ord, self.mesh, self.caps, store_caps,
                level_caps),
            self._jaxprof)
        store, idiag = init_step(out)
        if int(idiag["overflow"]):
            raise ValueError(
                f"initial WCOJ match store overflowed caps "
                f"({int(idiag['overflow'])} entries); re-register with a "
                "larger store_headroom")
        self._make_entry(name, meta, store, store_caps, list_step=list_step,
                         wcoj_level_caps=level_caps)
        self._counts[name] = int(idiag["count"])
        return self._counts[name]

    def _calibrate_wcoj_caps(self, plan: CompiledPlan):
        """Register-time calibration probe: replace the compile-time
        (estimator-derived) per-level WCOJ caps with the *observed*
        per-partition level sizes. One host pass over the same
        partitions the devices hold
        (:func:`~repro.core.match_engine.wcoj_level_counts`), so the
        unrolled device loop's intermediate tensors track real prefix
        sizes instead of estimator tails — shrinking hub-driven
        overestimates AND growing levels the degree-moment model
        undershoots (a planted dense core breaks Eq. 11 badly; the
        probe is ground truth at the register watermark either way).

        Returns ``(level_caps, store_group_floor)``: levels carry
        ``level_headroom`` (transient tensors, recoverable overflow),
        the store-group floor carries the bigger ``store_headroom``
        (persistent state, lossy overflow)."""
        from repro.core.match_engine import wcoj_level_counts

        storage = build_np_storage(self.graph, self.m)
        observed = [wcoj_level_counts(part, plan.wcoj, anchor_to_centers=True)
                    for part in storage.parts]
        peaks = [max((o[lvl] for o in observed), default=0)
                 for lvl in range(len(plan.wcoj_level_caps))]

        def pow2(x: int) -> int:
            n = 64
            while n < x:
                n *= 2
            return n

        return (tuple(pow2(int(self.level_headroom * p)) for p in peaks),
                pow2(int(self.store_headroom * peaks[-1])))

    def _make_entry(self, name, meta, store, store_caps, list_step=None,
                    wcoj_level_caps=None):
        """Common tail of register/restore/install: cold-fill the
        unit-table carry and fold the pattern into the fused maintain
        megastep. ``store_caps`` may exceed ``meta.plan.store_caps`` (a
        restore grows them to fit a concrete snapshot table). WCOJ-mode
        entries skip the carry entirely (their megastep slot re-derives
        patches from Φ(d') alone): empty carry pytree, no-op refresh."""
        prog = meta.plan.program
        unit_caps = meta.plan.unit_caps
        if meta.plan.executor == "wcoj":
            self._carry_stash.pop(name, None)   # wcoj mode has no carry
            if wcoj_level_caps is None:
                wcoj_level_caps, _ = self._calibrate_wcoj_caps(meta.plan)
            entry = _ShardedEntry(
                meta=meta, prog=prog,
                full_skel=meta.plan.storage_cover,
                store=store, store_caps=store_caps,
                unit_caps=unit_caps, carry={}, n_unit_plans=0,
                refresh_step=lambda pt: ({}, {"overflow": 0}),
                list_step=list_step, wcoj_level_caps=wcoj_level_caps,
            )
            self.entries[name] = entry
            self._rebuild_maintain_step()
            return entry
        refresh_step = ProfiledStep(
            f"unit_refresh:{name}",
            self._sharded.make_unit_refresh_step(
                prog, list(meta.units), self.mesh, self.caps, unit_caps),
            self._jaxprof)
        n_plans = len(self._sharded.unit_plan_registry(prog, list(meta.units))[0])
        stash = self._carry_stash.pop(name, None)
        if stash is not None and self._carry_compatible(stash, meta, unit_caps):
            # Same-watermark plan swap preserving everything the carry
            # depends on (cover, ord, units, unit caps — the chain order
            # is tree-independent): the removed entry's device carry is
            # still exactly right, so skip the cold re-listing entirely.
            carry = stash.carry
            self._obs().metrics.counter(
                "plan_swap_carry_reuses_total",
                "unit-table carries reused across cover-preserving swaps",
            ).inc()
            probe_inc("cache_hits", self.m * n_plans,
                      metrics=self._obs().metrics)
        else:
            carry, rdiag = refresh_step(self.pt)
            if int(rdiag["overflow"]):
                raise ValueError(
                    f"unit-table carry overflowed caps ({int(rdiag['overflow'])} "
                    "entries); enlarge EngineCaps / unit_table_caps headroom")
            # The cold fill lists every unit on every device once — the
            # same accounting as a host-cache cold miss.
            probe_inc("cache_misses", self.m * n_plans,
                      metrics=self._obs().metrics)
        entry = _ShardedEntry(
            meta=meta, prog=prog,
            full_skel=prog.nodes[prog.root].skel_cols,
            store=store, store_caps=store_caps,
            unit_caps=unit_caps, carry=carry, n_unit_plans=n_plans,
            refresh_step=refresh_step, list_step=list_step,
        )
        self.entries[name] = entry
        self._rebuild_maintain_step()
        return entry

    @staticmethod
    def _carry_compatible(stash: _ShardedEntry, meta: PatternMeta,
                          unit_caps) -> bool:
        """True when a stashed entry's unit-table carry is byte-valid
        for the new plan: the carry depends only on (cover, ord, units,
        unit caps) — the Nav-join chain order comes from
        ``left_deep_order(units, ·, cover)``, never the tree shape — and
        only tree-mode plans have one at all."""
        old = stash.meta
        return (old.plan is not None and old.plan.executor != "wcoj"
                and meta.plan.executor != "wcoj"
                and old.cover == meta.cover
                and old.ord_ == meta.ord_
                and len(old.units) == len(meta.units)
                and all(a.pattern.key() == b.pattern.key()
                        and a.anchors == b.anchors
                        for a, b in zip(old.units, meta.units))
                and stash.unit_caps == unit_caps)

    def _rebuild_maintain_step(self) -> None:
        """(Re)compile the fused megastep over the current entry set.

        Called whenever the set of patterns or any store caps change
        (register/remove/install/restore/resize). Always the same
        ``ProfiledStep`` name — recompiles accumulate into the single
        ``maintain_mega`` profile, whose ``subs`` attribute carries the
        per-pattern Eq.-11 cost shares used to attribute the fused
        latency (no per-pattern ghost steps)."""
        if not self.entries:
            self.maintain_step = None
            self._maintain_subs = {}
            return
        specs = [self._sharded.MaintainSpec(
            name=n, prog=e.prog, units=tuple(e.meta.units),
            store=e.store_caps, unit_caps=e.unit_caps,
            wcoj=(e.meta.plan.wcoj
                  if e.meta.plan.executor == "wcoj" else None),
            wcoj_level_caps=e.wcoj_level_caps)
            for n, e in self.entries.items()]
        costs = {n: (max(float(e.meta.plan.cost), 1e-9)
                     if e.meta.plan is not None else 1.0)
                 for n, e in self.entries.items()}
        total = sum(costs.values())
        self._maintain_subs = {n: c / total for n, c in costs.items()}
        self.maintain_step = ProfiledStep(
            "maintain_mega",
            self._sharded.make_maintain_mega_step(specs, self.mesh, self.caps),
            self._jaxprof, subs=self._maintain_subs)

    def restore_pattern(self, name: str, pattern: Pattern,
                        cover: Tuple[int, ...], table) -> int:
        """Rebuild a pattern's device state from a snapshot table: the
        :class:`~repro.dist.sharded.MatchStore` comes from
        ``stack_matches`` (no from-scratch listing), the unit-table
        carry from one refresh over the restored Φ."""
        return self.install_plan(name, self.compile(pattern, cover), table)

    def install_plan(self, name: str, plan: CompiledPlan, table) -> int:
        import jax
        from jax.sharding import NamedSharding

        if name in self.entries:
            raise ValueError(f"pattern {name!r} already registered")
        if table.cover != plan.storage_cover:
            # Snapshot from a different cover or executor mode (WCOJ
            # stores under trivial compression) — recompress to the
            # plan's storage layout before stacking onto the mesh.
            cols, rows = table.decompress(plan.ord)
            table = compress_table(plan.pattern, plan.storage_cover, cols, rows)
        meta = _meta_from_plan(name, plan)
        store_caps = quantize_store_caps(self._fit_store_caps(plan.store_caps, table))
        specs = self._sharded.match_specs(self.mesh, plan.pattern, plan.storage_cover)
        store = jax.device_put(
            self._sharded.stack_matches(table, self.m, store_caps),
            jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs))
        self._make_entry(name, meta, store, store_caps)
        self._counts[name] = table.count_matches(plan.ord)
        return self._counts[name]

    def remove_pattern(self, name: str) -> None:
        # Stash the removed entry until the next batch: a plan swap
        # (remove → install at the same committed watermark) can reuse
        # its unit-table carry when the new plan preserves everything
        # the carry depends on (see _make_entry).
        self._carry_stash[name] = self.entries[name]
        del self.entries[name]        # drops the device store/carry refs
        del self._counts[name]
        self._rebuild_maintain_step()

    def _fit_store_caps(self, est, table):
        """Grow estimator-sized StoreCaps to hold a concrete snapshot
        table (stack_matches fail-stops on a misfit — a restore must
        never lose groups to a sizing guess)."""
        if table.n_groups == 0:
            return est
        owner = self._sharded._owner_rows_np(
            table.skeleton.astype(np.int64), self.m)
        need_g = int(np.bincount(owner, minlength=self.m).max())
        need_s = max((int(r.counts().max(initial=0))
                      for r in table.comp.values()), default=1)

        def up(x, align):
            return int(-(-max(1, int(x)) // align) * align)

        return self._sharded.StoreCaps(
            group_cap=max(est.group_cap, up(need_g, 64)),
            set_cap=max(est.set_cap, up(need_s, 8)))

    def meta(self, name: str) -> PatternMeta:
        return self.entries[name].meta

    def names(self) -> List[str]:
        return list(self.entries)

    def count(self, name: str) -> int:
        return self._counts[name]

    @staticmethod
    def _storage_cover(e: _ShardedEntry) -> Tuple[int, ...]:
        """The cover the entry's *store layout* uses — all vertices for
        WCOJ-mode (trivial compression), the compile cover otherwise."""
        return (e.meta.plan.storage_cover
                if e.meta.plan is not None else e.meta.cover)

    def materialize(self, name: str):
        """Lazy device→host pull of the running match set (cached until
        the next committed batch moves the store).

        Only each shard's **valid prefix** transfers
        (:meth:`_flatten_live`): the store's canonical layout packs
        live groups first, so the pull costs O(live table), not
        O(StoreCaps) — a ``wants_matches`` sink that needs the
        pre-batch table every batch pays for what it reads, and
        ``host_transfer_bytes_total`` reflects actual data moved.
        """
        e = self.entries[name]
        if e.host_table is None:
            obs = self._obs()
            b0 = self.last_host_bytes
            with obs.tracer.span("materialize", pattern=name) as sp:
                e.host_table = self._je.comp_to_host(
                    self._flatten_live(e.store.as_comp()), e.meta.pattern,
                    self._storage_cover(e), e.full_skel)
                sp.add("host_bytes", self.last_host_bytes - b0)
            probe_inc("host_materializations", metrics=obs.metrics)
        return e.host_table

    def _flatten_live(self, tc):
        """Pull only each shard's valid prefix of stacked [M, G, ...]
        compressed tensors (device-side compaction before transfer).

        Engine merge/group outputs pack live groups first, so slicing
        ``arr[i, :k]`` on device and pulling the slice moves O(delta)
        bytes instead of the cap-padded O(StoreCaps) tensors. Any shard
        that is *not* prefix-packed (foreign layout) falls back to the
        exact full-tensor pull — correctness never depends on packing.
        """
        valid = self._pull(tc.valid)
        m = valid.shape[0]
        ks = [int(k) for k in valid.reshape(m, -1).sum(axis=1)]
        if not all(bool(valid[i, :ks[i]].all()) for i in range(m)):
            return self._flatten(tc)
        skel = np.concatenate(
            [self._pull(tc.skeleton[i, :ks[i]]) for i in range(m)], axis=0)
        sets = {key: np.concatenate(
                    [self._pull(v[i, :ks[i]]) for i in range(m)], axis=0)
                for key, v in tc.sets.items()}
        return self._je.CompTensors(
            skeleton=skel, valid=np.ones(skel.shape[0], bool), sets=sets)

    def matches_plain(self, name: str) -> np.ndarray:
        e = self.entries[name]
        return self.materialize(name).decompress(e.meta.ord_)[1]

    def _pad(self, edges: np.ndarray, cap: int):
        import jax.numpy as jnp
        k = edges.shape[0]
        if k > cap:
            raise ValueError(f"batch has {k} edges > static cap {cap}")
        out = np.full((cap, 2), -1, np.int32)
        out[:k] = edges
        return jnp.asarray(out)

    def apply_batch(self, delta: SharedDelta, want_matches) -> Dict[str, PatternReport]:
        obs = self._obs()
        tr = obs.tracer
        upd = delta.update
        # Per-batch diagnostics: reset before any work so a short
        # circuit (or a failure) can't leak last batch's numbers.
        self.last_storage_overflow = 0
        self.last_cand_vertices = -1
        self.last_cand_edges = -1
        self.last_host_bytes = 0
        self.last_cache_hits = 0
        self.last_cache_misses = 0
        self.last_invalidated_parts = 0
        # Stashed carries are pinned to the committed watermark — once a
        # batch runs, Φ moves and they can never be reused.
        self._carry_stash.clear()
        if upd.size == 0:
            return self._noop_reports()
        add = self._pad(np.asarray(upd.add), self.ushapes.n_add)
        dele = self._pad(np.asarray(upd.delete), self.ushapes.n_del)
        # Device Alg. 4 — once per batch, shared by every pattern. The
        # journal-netted SharedDelta codes are what the delta-restricted
        # step consumes: candidate sets are derived from exactly these
        # endpoints.
        with tr.span("storage_update") as ssp:
            pt2, sdiag = self.storage_step(self.pt, add, dele)
            self.last_storage_overflow = int(sdiag["overflow"])
            self.last_cand_vertices = int(sdiag.get("cand_vertices", -1))
            self.last_cand_edges = int(sdiag.get("cand_edges", -1))
            if int(sdiag.get("cand_overflow", 0)) and self.ushapes.cand_cap is not None:
                # Estimator-sized candidate caps outran by this delta
                # (e.g. a hub-concentrated batch) — gated on the
                # candidate-cap counter specifically: e_cap/deg_cap/oob
                # overflow also lands in the summed counter, and no
                # candidate resize can fix those. Nothing has been
                # committed: fall back to the never-overflow derivation
                # permanently (one recompile) and retry the same batch
                # exactly.
                self.cap_fallbacks += 1
                obs.metrics.counter(
                    "sharded_cap_fallbacks_total",
                    "permanent fallbacks to never-overflow candidate caps",
                ).inc()
                ssp.add("cap_fallbacks", 1)
                self.ushapes = self._sharded.UpdateShapes(
                    n_add=self._max_add, n_del=self._max_del)
                # Same step name on purpose: the recompile folds into
                # the existing "storage_update" StepProfile.
                self.storage_step = ProfiledStep(
                    "storage_update",
                    self._sharded.make_storage_update_step(
                        self.mesh, self.caps, self.ushapes,
                        mode=self.update_mode),
                    self._jaxprof)
                pt2, sdiag = self.storage_step(self.pt, add, dele)
                self.last_storage_overflow = int(sdiag["overflow"])
                self.last_cand_vertices = int(sdiag.get("cand_vertices", -1))
                self.last_cand_edges = int(sdiag.get("cand_edges", -1))
            ssp.add("overflow", self.last_storage_overflow)
        if self.strict_overflow and self.last_storage_overflow:
            # Dropped candidates mean Φ(d') is missing patches — wrong
            # forever, not just this batch. Nothing has been committed
            # yet; abort loudly instead.
            raise RuntimeError(
                f"device storage update overflowed caps "
                f"({self.last_storage_overflow} entries) — counts would be "
                "silently wrong from here on. Enlarge EngineCaps, or pass "
                "strict_overflow=False to tolerate undercounts.")
        dirty = sdiag["part_dirty"]
        names = list(self.entries)
        reports: Dict[str, PatternReport] = {}
        if names:
            before = dict(self._counts)
            # Removed rows need the pre-update tables — materialized
            # (and byte-accounted) only when a sink asked for rows AND
            # the netted batch actually deletes something. Must happen
            # BEFORE the megastep: it donates the store buffers.
            removed_by: Dict[str, Optional[np.ndarray]] = {
                name: (removed_rows(self.materialize(name), upd.delete,
                                    self.entries[name].meta.ord_)
                       if name in want_matches and np.asarray(upd.delete).size
                       else None)
                for name in names}
            # ONE fused maintain dispatch for every pattern: per
            # pattern, refresh ∘ patch ∘ filter ∘ merge ∘ count; all
            # stores, patches and unit-table carries stay device
            # arrays, and the updated partitions + delete table are
            # shared across patterns inside the step. Only devices
            # whose partition the storage step dirtied re-list their
            # unit tables.
            t0 = time.perf_counter()
            with tr.span("maintain_mega", patterns=len(names)) as msp:
                stores = {n: self.entries[n].store for n in names}
                carries = {n: self.entries[n].carry for n in names}
                stores2, patches, carries2, mdiag = self.maintain_step(
                    pt2, stores, carries, dirty, add, dele)
                if (not self.strict_overflow and
                        any(int(mdiag[n]["store_overflow"]) for n in names)):
                    # Some running store outgrew its caps. Nothing has
                    # committed (self.pt/self._counts untouched):
                    # double the overflowing patterns' caps, rebuild
                    # the pre-batch stores, recompile the megastep and
                    # retry the same batch. Gated on store_overflow —
                    # the StoreCaps share of the counter — because
                    # engine-cap overflow in the summed counter can't
                    # be fixed by a store resize.
                    stores2, patches, carries2, mdiag = \
                        self._resize_stores_and_retry(pt2, dirty, add, dele,
                                                      mdiag, carries2)
                if self.strict_overflow and any(
                        int(mdiag[n]["overflow"]) for n in names):
                    # A dropped store group is a match set lost forever
                    # (no later patch re-derives it) — refuse to commit
                    # the lossy batch. The megastep may have consumed
                    # (donated) the store/carry inputs, so rebuild the
                    # committed-watermark state from the never-donated
                    # partitions before raising: the backend stays
                    # usable, nothing has advanced.
                    overfull = [n for n in names if int(mdiag[n]["overflow"])]
                    self._rebuild_stores_from_partitions()
                    for e2 in self.entries.values():
                        e2.carry = e2.refresh_step(self.pt)[0]
                    raise RuntimeError(
                        f"maintain step for {overfull!r} overflowed device "
                        f"caps — the running match set would silently lose "
                        "groups. Re-register with a larger store_headroom / "
                        "EngineCaps, or pass strict_overflow=False for "
                        "best-effort auto-resize.")
                msp.add("store_groups",
                        sum(int(mdiag[n]["store_groups"]) for n in names))
            lat = time.perf_counter() - t0
            # Commit — the megastep is atomic across patterns: either
            # every store/carry/count advances or none did.
            for name in names:
                e = self.entries[name]
                e.store = stores2[name]
                e.carry = carries2[name]
                e.host_table = None   # the store moved on; drop the cache
                self._counts[name] = int(mdiag[name]["count"])
            for name in names:
                e = self.entries[name]
                d = mdiag[name]
                refreshed = int(d["unit_refreshes"])
                self.last_cache_hits += (self.m - refreshed) * e.n_unit_plans
                self.last_cache_misses += refreshed * e.n_unit_plans
                self.last_invalidated_parts = refreshed
                added = None
                if name in want_matches:
                    patch = self._je.comp_to_host(
                        self._flatten_live(patches[name]), e.meta.pattern,
                        self._storage_cover(e), e.full_skel)
                    added = patch.decompress(e.meta.ord_)[1]
                with tr.span("maintain", pattern=name) as psp:
                    psp.add("patch_groups", int(d["patch_groups"]))
                    psp.add("removed_groups", int(d["removed_groups"]))
                    psp.add("overflow", int(d["overflow"]))
                    psp.add("unit_refreshes", refreshed)
                reports[name] = PatternReport(
                    name=name, count_before=before[name],
                    count_after=self._counts[name],
                    # The fused step is timed once; per-pattern latency
                    # is the Eq.-11 cost share of the fused wall-clock
                    # (the same shares the profiler publishes in subs).
                    latency_s=lat * self._maintain_subs.get(
                        name, 1.0 / len(names)),
                    patch_groups=int(d["patch_groups"]),
                    removed_groups=int(d["removed_groups"]),
                    overflow=int(d["overflow"]),
                    added=added,
                    removed=removed_by[name],
                )
        self.pt = pt2
        self.graph = self.graph.apply_update(upd)
        probe_inc("cache_hits", self.last_cache_hits, metrics=obs.metrics)
        probe_inc("cache_misses", self.last_cache_misses, metrics=obs.metrics)
        probe_inc("invalidated_parts", self.last_invalidated_parts,
                  metrics=obs.metrics)
        return reports

    def _rebuild_stores_from_partitions(self) -> None:
        """Recreate every pattern's committed-watermark MatchStore by
        re-listing over the never-donated partitions ``self.pt``.

        The donation-era replacement for rebuilding from
        ``materialize()``: after a failed megastep the store inputs may
        already be consumed, but Φ at the committed watermark is
        intact, and the initial-calculation pipeline regenerates the
        same canonical store shards (grouping and merge both
        canonicalize by skeleton key under the same ownership hash).
        Raises if the re-listing itself outruns the engine caps — that
        cannot be fixed by a store resize.
        """
        for name, e in self.entries.items():
            wcoj = (e.meta.plan.wcoj
                    if e.meta.plan.executor == "wcoj" else None)
            if e.list_step is None:
                # Patterns installed from a snapshot never listed; the
                # step is compiled on first rebuild and kept.
                e.list_step = ProfiledStep(
                    f"list:{name}",
                    (self._sharded.make_wcoj_list_step(
                        e.meta.pattern, wcoj, self.mesh, self.caps,
                        e.wcoj_level_caps)
                     if wcoj is not None else
                     self._sharded.make_list_step(e.prog, self.mesh, self.caps)),
                    self._jaxprof)
            out, ldiag = e.list_step(self.pt)
            if int(ldiag["overflow"]):
                raise RuntimeError(
                    f"re-listing {name!r} while rebuilding its store "
                    f"overflowed engine caps ({int(ldiag['overflow'])} rows); "
                    "enlarge EngineCaps")
            init_step = ProfiledStep(
                f"init_store:{name}",
                (self._sharded.make_wcoj_init_store_step(
                    e.meta.pattern, e.meta.ord_, self.mesh, self.caps,
                    e.store_caps, e.wcoj_level_caps)
                 if wcoj is not None else
                 self._sharded.make_init_store_step(
                    e.prog, self.mesh, self.caps, e.store_caps)),
                self._jaxprof)
            store, idiag = init_step(out)
            if int(idiag["overflow"]):
                raise RuntimeError(
                    f"rebuilding {name!r}'s store overflowed its caps "
                    f"({int(idiag['overflow'])} entries)")
            e.store = store
            e.host_table = None

    def _resize_stores_and_retry(self, pt2, dirty, add, dele, mdiag, carries2):
        """Best-effort self-healing, megastep edition: double the
        (quantized) caps of every overflowing pattern, rebuild ALL
        pre-batch stores from the never-donated partitions (the donated
        inputs are consumed), recompile the fused step under the same
        ``maintain_mega`` profile, retry the batch — until the store
        share of the overflow clears or the retry budget is spent
        (engine-cap overflow survives and stays a counted metric).

        The retry reuses the failed attempt's carry *outputs*: the
        carry half of the megastep depends only on Φ(d') and the dirty
        flags, never on the stores, so those outputs are already
        correct for this batch (and refreshing is idempotent).
        """
        out = None
        for _ in range(self._max_store_resizes):
            over = [n for n in self.entries
                    if int(mdiag[n]["store_overflow"])]
            if not over:
                break
            for name in over:
                e = self.entries[name]
                self.store_resizes += 1
                self._obs().metrics.counter(
                    "sharded_store_resizes_total",
                    "MatchStore ×2-cap rebuilds after store overflow",
                ).inc()
                e.store_caps = quantize_store_caps(self._sharded.StoreCaps(
                    group_cap=2 * e.store_caps.group_cap,
                    set_cap=2 * e.store_caps.set_cap))
            self._rebuild_stores_from_partitions()
            self._rebuild_maintain_step()
            stores = {n: e.store for n, e in self.entries.items()}
            out = self.maintain_step(pt2, stores, carries2, dirty, add, dele)
            mdiag = out[3]
            carries2 = out[2]
        if out is None:
            raise AssertionError("resize called without store overflow")
        return out


# ---------------------------------------------------------------------------
# The service
# ---------------------------------------------------------------------------

class ListingService:
    """Continuous multi-pattern subgraph listing over a dynamic graph.

    ``ingest()`` appends edge operations to the journal (validated
    against the *projected* graph — the committed graph plus everything
    pending); ``advance()`` folds pending operations into every
    registered pattern's match set in scheduler-chosen micro-batches,
    computing the decoded update delta **once per batch**; ``counts()``
    reads the live results. Sinks observe per-batch result deltas;
    ``audit_every > 0`` re-lists one pattern from scratch every N
    batches and raises on divergence.
    """

    def __init__(
        self,
        graph: Graph,
        m: int = 4,
        backend: str | StreamBackend = "host",
        scheduler: BatchScheduler | None = None,
        audit_every: int = 0,
        obs: Observability | None = None,
        plan_manager=None,
        **backend_kwargs,
    ):
        # One observability object per service — its own metrics
        # registry (two services in one process never share counters),
        # span tracer (off by default), device profiler. Pass
        # Observability.full() for span tracing, .disabled() to turn
        # every channel off.
        self.obs = obs if obs is not None else Observability()
        if isinstance(backend, str):
            if backend == "host":
                backend_obj: StreamBackend = HostBackend(graph, m=m, **backend_kwargs)
            elif backend == "sharded":
                # `m` here is the host partition count; the sharded mesh
                # size defaults to the device count — pass m explicitly
                # via backend_kwargs to override it.
                backend_obj = ShardedBackend(graph, **backend_kwargs)
            else:
                raise ValueError(f"unknown backend {backend!r}")
        else:
            backend_obj = backend
        self.backend = backend_obj
        # Attach before any register() call so initial listings and
        # device-step compiles are profiled into this service's books.
        self.backend.obs = self.obs
        self.journal = UpdateJournal()
        self.scheduler = scheduler if scheduler is not None else BatchScheduler()
        if self.backend.max_batch_ops is not None:
            self.scheduler.clamp_max_ops(self.backend.max_batch_ops)
        self.audit_every = int(audit_every)
        self.metrics: List[BatchMetrics] = []
        self.audits: List[Tuple[int, str, bool]] = []   # (batch_index, pattern, ok)
        self.sinks: List[Sink] = []
        self._graph = graph                   # committed graph mirror
        self._proj_codes = set(int(c) for c in graph.codes)
        self._proj_n = graph.n
        self._committed = 0
        self._batches = 0
        self._audit_rr = 0
        #: optional drift-triggered online re-optimizer
        #: (:class:`repro.stream.plan_manager.PlanManager`); consulted
        #: after every committed batch.
        self.plan_manager = plan_manager

    # -------------------------------------------------------------- patterns
    def register(self, name: str, pattern: Pattern, cover=None) -> int:
        """Register a pattern; returns its initial match count.

        Patterns join at the *committed* watermark: the initial listing
        runs over the committed graph, and pending journal operations
        apply to the new pattern on the next :meth:`advance` like to
        every other.
        """
        count = self.backend.register(name, pattern, cover)
        meta = self.backend.meta(name)
        self.scheduler.register(name, pattern, meta.ord_, meta.units)
        self.scheduler.refresh(GraphStats.of(self._graph))
        if meta.plan is not None:
            self.obs.record_plan(name, meta.plan.to_json())
        return count

    def patterns(self) -> List[str]:
        return self.backend.names()

    # ---------------------------------------------------------------- ingest
    def ingest(self, update: GraphUpdate | None = None, *,
               add: Iterable = (), delete: Iterable = ()) -> int:
        """Append one update to the journal; returns the tail watermark.

        Validated against the projected graph so any window of the
        journal nets to a well-formed Alg. 4 batch.
        """
        if update is None:
            update = GraphUpdate.make(delete=delete, add=add)
        d_codes = [int(c) for c in edge_codes(np.asarray(update.delete))]
        a_codes = [int(c) for c in edge_codes(np.asarray(update.add))]
        # Duplicates inside one update would double-journal an op and
        # flip the parity netting, desyncing projection from commit.
        if len(set(d_codes)) != len(d_codes) or len(set(a_codes)) != len(a_codes):
            raise ValueError("update contains duplicate edges")
        for c in d_codes:
            if c not in self._proj_codes:
                raise ValueError(f"delete of absent edge {tuple(decode_edges(np.array([c]))[0])}")
        for c in a_codes:
            if c in self._proj_codes:
                raise ValueError(f"insert of present edge {tuple(decode_edges(np.array([c]))[0])}")
        if len(set(d_codes) & set(a_codes)):
            raise ValueError("E_d(U) and E_a(U) must be disjoint")
        self._proj_codes.difference_update(d_codes)
        self._proj_codes.update(a_codes)
        if np.asarray(update.add).size:
            self._proj_n = max(self._proj_n, int(np.asarray(update.add).max()) + 1)
        return self.journal.append(update)

    # --------------------------------------------------------------- advance
    def _wanted(self) -> set:
        want = set()
        for s in self.sinks:
            if s.wants_matches:
                for name in self.backend.names():
                    if s.accepts(name):
                        want.add(name)
        return want

    def advance(self, watermark: int | None = None) -> List[BatchMetrics]:
        """Fold pending journal ops (up to ``watermark``) into all match
        sets, one scheduler-sized micro-batch at a time."""
        target = self.journal.tail if watermark is None else min(int(watermark), self.journal.tail)
        done: List[BatchMetrics] = []
        want = self._wanted()
        tr = self.obs.tracer
        mreg = self.obs.metrics
        while self._committed < target:
            k = self.scheduler.next_batch_size(target - self._committed)
            hi = self._committed + k
            predicted = self.scheduler.predict_seconds(k)
            self.obs.jaxprof.on_batch_start(self._batches)
            with tr.span("batch", batch_index=self._batches,
                         lo=self._committed, hi=hi) as bsp:
                t0 = time.perf_counter()
                with tr.span("shared_delta") as dsp:
                    delta = compute_shared_delta(self.journal, self._committed,
                                                 hi, metrics=mreg)
                    dsp.add("net_add", int(np.asarray(delta.update.add).shape[0]))
                    dsp.add("net_delete",
                            int(np.asarray(delta.update.delete).shape[0]))
                reports = self.backend.apply_batch(delta, want)
                latency = time.perf_counter() - t0
                self.scheduler.observe(k, latency)
                # Both backends already advanced their committed graph
                # while applying the batch — reuse it instead of a
                # second rebuild.
                self._graph = self.backend.graph
                # host backend shares the delta's stats; the sharded
                # backend never materializes Φ(d') on host, so refresh
                # from the mirror
                self.scheduler.refresh(
                    delta.stats if delta.stats is not None else GraphStats.of(self._graph))
                bm = BatchMetrics(
                    batch_index=self._batches, lo=self._committed, hi=hi,
                    n_ops=k, net_add=int(np.asarray(delta.update.add).shape[0]),
                    net_delete=int(np.asarray(delta.update.delete).shape[0]),
                    latency_s=latency, patterns=reports,
                    storage_overflow=getattr(self.backend, "last_storage_overflow", 0),
                    cand_vertices=getattr(self.backend, "last_cand_vertices", -1),
                    cand_edges=getattr(self.backend, "last_cand_edges", -1),
                    host_bytes=getattr(self.backend, "last_host_bytes", 0),
                    cache_hits=getattr(self.backend, "last_cache_hits", -1),
                    cache_misses=getattr(self.backend, "last_cache_misses", -1),
                    invalidated_parts=getattr(self.backend, "last_invalidated_parts", -1),
                    predicted_s=predicted if predicted is not None else -1.0,
                )
                if bm.cache_hits >= 0:
                    # Calibrate the scheduler's warm `fixed` term from
                    # the observed unit-cache traffic (no-op batches
                    # carry none).
                    self.scheduler.observe_cache(bm.cache_hits, bm.cache_misses)
                self._record_batch(bm, bsp)
                self.metrics.append(bm)
                done.append(bm)
                self._committed = hi
                self._batches += 1
                with tr.span("sinks") as ksp:
                    self._emit(bm, delta)
                    ksp.add("sinks", len(self.sinks))
            self.obs.jaxprof.on_batch_end(self._batches - 1)
            if self.audit_every and self._batches % self.audit_every == 0:
                self._periodic_audit()
            if self.plan_manager is not None:
                # Between batches = at the committed watermark, the only
                # point where a plan swap is collective-safe.
                self.plan_manager.on_batch(self)
        return done

    def _record_batch(self, bm: BatchMetrics, bsp) -> None:
        """Fold one committed batch into the service's instruments (and
        annotate its root span so span counters reconcile with registry
        deltas — asserted in tests)."""
        m = self.obs.metrics
        m.counter("stream_batches_total", "committed micro-batches").inc()
        m.counter("stream_ops_total", "journal ops committed").inc(bm.n_ops)
        m.gauge("stream_watermark_lag",
                "journal ops ingested but not yet committed",
                ).set(self.journal.tail - bm.hi)
        if bm.latency_s > 0:
            # Below-clock-resolution batches carry no rate signal: they
            # are excluded from the throughput gauge and the latency
            # histogram rather than rendering as infinities.
            m.histogram("stream_batch_latency_seconds",
                        "end-to-end latency per committed micro-batch",
                        ).observe(bm.latency_s)
            m.gauge("stream_throughput_ops_per_s",
                    "ops/s of the last measurable batch",
                    ).set(bm.throughput_ops_s)
        for name, rep in bm.patterns.items():
            if rep.latency_s > 0:
                m.histogram("stream_pattern_latency_seconds",
                            "per-pattern maintain latency",
                            labels=("pattern",),
                            ).labels(pattern=name).observe(rep.latency_s)
        if bm.overflow:
            m.counter("stream_overflow_total",
                      "summed device cap overflow across batches",
                      ).inc(bm.overflow)
        if bm.cand_vertices >= 0:
            m.gauge("stream_cand_vertices",
                    "candidate vertex-set size of the last delta batch",
                    ).set(bm.cand_vertices)
            m.gauge("stream_cand_edges",
                    "candidate edge-set size of the last delta batch",
                    ).set(bm.cand_edges)
        if bm.predicted_s >= 0:
            m.gauge("scheduler_predicted_seconds",
                    "§IV-D model prediction for the last batch",
                    ).set(bm.predicted_s)
        drift = self.scheduler.drift()
        if drift is not None:
            m.gauge("scheduler_drift_ewma",
                    "EWMA of observed/predicted batch latency — the "
                    "cost-model drift sensor for plan re-optimization",
                    ).set(drift)
        # Root-span counters mirror the registry deltas of this batch.
        bsp.add("n_ops", bm.n_ops)
        bsp.add("net_add", bm.net_add)
        bsp.add("net_delete", bm.net_delete)
        bsp.add("host_bytes", bm.host_bytes)
        if bm.cache_hits >= 0:
            bsp.add("cache_hits", bm.cache_hits)
            bsp.add("cache_misses", bm.cache_misses)
            bsp.add("invalidated_parts", bm.invalidated_parts)

    def _emit(self, bm: BatchMetrics, delta: SharedDelta) -> None:
        for name, rep in bm.patterns.items():
            accepting = [s for s in self.sinks if s.accepts(name)]
            if not accepting:
                continue
            ev = BatchEvent(
                batch_index=bm.batch_index, lo=bm.lo, hi=bm.hi, pattern=name,
                count_before=rep.count_before, count_after=rep.count_after,
                n_ops=bm.n_ops, net_add=bm.net_add, net_delete=bm.net_delete,
                latency_s=rep.latency_s, overflow=rep.overflow,
                added=rep.added, removed=rep.removed,
            )
            for s in accepting:
                s.emit(ev)
            # Retained metrics keep scalars only; the decompressed row
            # deltas live as long as the sinks want them, not forever.
            rep.added = None
            rep.removed = None

    # ---------------------------------------------------------------- results
    def count(self, name: str) -> int:
        return self.backend.count(name)

    def counts(self) -> Dict[str, int]:
        return {name: self.backend.count(name) for name in self.backend.names()}

    def subscribe(self, sink: Sink) -> Sink:
        self.sinks.append(sink)
        return sink

    # ----------------------------------------------------------------- state
    @property
    def committed_watermark(self) -> int:
        return self._committed

    @property
    def graph(self) -> Graph:
        """The committed graph (watermark ``committed_watermark``)."""
        return self._graph

    def projected_graph(self) -> Graph:
        """The graph at the journal tail (committed + pending)."""
        codes = np.array(sorted(self._proj_codes), np.int64)
        return Graph._from_codes(self._proj_n, codes)

    def compact(self) -> int:
        """Truncate the journal below the committed watermark."""
        return self.journal.truncate(self._committed)

    # ------------------------------------------------------------ durability
    _SNAP_MAGIC = "repro.stream.snapshot"

    def snapshot(self, path: str) -> str:
        """Persist the service at its committed watermark into ``path``.

        A snapshot is exactly *materialize() per pattern + journal
        save*: ``graph.npz`` (the committed graph), one
        ``matches_<name>.npz`` per pattern (its compressed match set —
        the sharded backend pulls it through the byte-accounted
        :meth:`~StreamBackend.materialize` contract), ``journal.jsonl``
        (including any ops still pending beyond the watermark — they
        replay after restore), and ``meta.json`` naming the watermark
        and the registered patterns. ``meta.json`` is written last and
        atomically, so its presence is the commit record: a crash
        mid-snapshot leaves no half-snapshot that :meth:`restore` would
        accept — and re-snapshotting into a used directory deletes the
        old ``meta.json`` *first*, so a crash mid-rewrite can never
        leave a stale commit record pointing at newer artifacts.
        """
        os.makedirs(path, exist_ok=True)
        meta_path = os.path.join(path, "meta.json")
        if os.path.exists(meta_path):
            os.remove(meta_path)
        self.journal.save(os.path.join(path, "journal.jsonl"))
        np.savez(os.path.join(path, "graph.npz"),
                 codes=np.asarray(self._graph.codes, np.int64),
                 n=np.int64(self._graph.n))
        patterns = []
        for name in self.backend.names():
            meta = self.backend.meta(name)
            _save_table(os.path.join(path, f"matches_{name}.npz"),
                        self.backend.materialize(name))
            patterns.append({
                "name": name,
                "edges": sorted([int(a), int(b)] for a, b in meta.pattern.edges),
                "cover": [int(c) for c in meta.cover],
            })
        head = {"kind": self._SNAP_MAGIC, "version": 1,
                "watermark": int(self._committed), "patterns": patterns}
        tmp = f"{meta_path}.tmp"
        with open(tmp, "w") as f:
            json.dump(head, f, indent=2, sort_keys=True)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, meta_path)
        return path

    @classmethod
    def restore(cls, path: str, backend: str | StreamBackend = "host",
                scheduler: BatchScheduler | None = None, audit_every: int = 0,
                **backend_kwargs) -> "ListingService":
        """Rebuild a service from a :meth:`snapshot` and resume.

        The backend is reconstructed over the snapshot graph and each
        pattern's match set is installed without a from-scratch listing
        (the sharded backend rebuilds its device
        :class:`~repro.dist.sharded.MatchStore` via ``stack_matches``
        and cold-fills its unit-table carries with one refresh step).
        Journal ops pending beyond the snapshot watermark survive and
        fold in on the next :meth:`advance` — the restored service is
        indistinguishable from one that never stopped (parity-tested).
        The restore backend may differ from the snapshot's (e.g. host
        snapshot → sharded restore): a snapshot is backend-neutral.
        """
        with open(os.path.join(path, "meta.json")) as f:
            head = json.load(f)
        if head.get("kind") != cls._SNAP_MAGIC:
            raise ValueError(f"{path} is not a service snapshot")
        if head.get("version") != 1:
            raise ValueError(
                f"{path}: unsupported snapshot version {head.get('version')!r}")
        gz = np.load(os.path.join(path, "graph.npz"))
        graph = Graph._from_codes(int(gz["n"]), gz["codes"].astype(np.int64))
        svc = cls(graph, backend=backend, scheduler=scheduler,
                  audit_every=audit_every, **backend_kwargs)
        svc.journal = UpdateJournal.load(os.path.join(path, "journal.jsonl"))
        w = int(head["watermark"])
        if w < svc.journal.base:
            raise ValueError(
                f"snapshot watermark {w} precedes journal base {svc.journal.base}")
        svc._committed = w
        for spec in head["patterns"]:
            pat = Pattern.make([tuple(e) for e in spec["edges"]])
            table = _load_table(
                os.path.join(path, f"matches_{spec['name']}.npz"), pat)
            svc.backend.restore_pattern(
                spec["name"], pat, tuple(int(c) for c in spec["cover"]), table)
            meta = svc.backend.meta(spec["name"])
            svc.scheduler.register(spec["name"], pat, meta.ord_, meta.units)
            if meta.plan is not None:
                svc.obs.record_plan(spec["name"], meta.plan.to_json())
        svc.scheduler.refresh(GraphStats.of(graph))
        if svc.journal.tail > w:
            # pending ops re-project on top of the committed graph
            proj = graph.apply_update(svc.journal.window(w))
            svc._proj_codes = {int(c) for c in proj.codes}
            svc._proj_n = proj.n
        return svc

    # ----------------------------------------------------------------- audit
    def audit(self, names: Sequence[str] | None = None,
              raise_on_mismatch: bool = True) -> Dict[str, bool]:
        """From-scratch re-listing on the committed graph vs. live counts."""
        out = {}
        for name in (names if names is not None else self.backend.names()):
            meta = self.backend.meta(name)
            fresh = DDSL(self._graph, meta.pattern, m=4, cover=meta.cover)
            fresh.initial()
            ok = fresh.count() == self.backend.count(name)
            out[name] = ok
            if not ok and raise_on_mismatch:
                raise RuntimeError(
                    f"audit mismatch for {name!r}: incremental={self.backend.count(name)} "
                    f"from-scratch={fresh.count()} at watermark {self._committed}")
        return out

    def _periodic_audit(self) -> None:
        names = self.backend.names()
        if not names:
            return
        name = names[self._audit_rr % len(names)]
        self._audit_rr += 1
        # Record the verdict first so a divergence is visible in
        # `audits` even though it also aborts the service.
        ok = self.audit([name], raise_on_mismatch=False)[name]
        self.audits.append((self._batches - 1, name, ok))
        if not ok:
            raise RuntimeError(
                f"periodic audit mismatch for {name!r} at watermark {self._committed}")
