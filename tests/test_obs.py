"""Observability stack: metrics registry, span tracer, device profiling.

Covers the `repro.obs` instruments in isolation (golden Prometheus
exposition, ProbeView shim semantics, span-tree mechanics,
compile-vs-execute attribution) and threaded through the streaming
service (span skeleton per batch on both backends, span counters
reconciled against registry deltas, unit-cache LRU budget accounting,
scheduler drift gauge).
"""

import json

import numpy as np
import pytest
from conftest import random_graph

from repro.core.pattern import PATTERN_LIBRARY
from repro.data.graphs import GraphUpdate, sample_update
from repro.obs import (
    DEFAULT_LATENCY_BUCKETS,
    JaxProfiler,
    MetricsRegistry,
    Observability,
    ProbeView,
    ProfiledStep,
    Tracer,
)
from repro.obs.trace import NULL_SPAN
from repro.stream import BatchMetrics, BatchScheduler, ListingService
from repro.stream import scheduler as stream_scheduler


def _stream(svc, rounds, d, a, seed0=0):
    for b in range(rounds):
        svc.ingest(sample_update(svc.projected_graph(), d, a, seed=seed0 + b))


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)

    lc = r.counter("lc_total", labels=("pattern",))
    lc.labels(pattern="tri").inc(4)
    lc.labels(pattern="sq").inc(1)
    assert lc.value_for(pattern="tri") == 4
    assert lc.value_for(pattern="absent") == 0
    with pytest.raises(ValueError):
        lc.labels(wrong="x")
    with pytest.raises(ValueError):
        lc.inc()            # labeled counter requires labels()

    g = r.gauge("g")
    g.set(2.0)
    g.inc()
    g.dec(0.5)
    assert g.value == 2.5

    h = r.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    cell = h.cell()
    assert cell.counts == [1, 2, 1]     # ≤0.1, ≤1.0, +Inf
    assert cell.count == 4
    assert cell.sum == pytest.approx(6.05)


def test_registry_idempotent_and_kind_conflicts():
    r = MetricsRegistry()
    c1 = r.counter("x_total", "first help wins")
    c2 = r.counter("x_total", "ignored")
    assert c1 is c2 and c1.help == "first help wins"
    with pytest.raises(TypeError):
        r.gauge("x_total")
    with pytest.raises(TypeError):
        r.histogram("x_total")
    assert sorted(r.names()) == ["x_total"]
    r.reset()
    assert r.names() == []
    # buckets must be ascending and unique
    with pytest.raises(ValueError):
        r.histogram("bad", buckets=(1.0, 0.5))


def test_golden_prometheus_exposition():
    """Exposition is deterministic text — byte-exact golden comparison."""
    r = MetricsRegistry()
    r.counter("a_total", "help a").inc(3)
    r.counter("b_total", labels=("p",)).labels(p="x").inc(2.5)
    r.gauge("g", "a gauge").set(1.5)
    h = r.histogram("h_seconds", "hist", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert r.to_prometheus() == (
        "# HELP a_total help a\n"
        "# TYPE a_total counter\n"
        "a_total 3\n"
        "# TYPE b_total counter\n"
        'b_total{p="x"} 2.5\n'
        "# HELP g a gauge\n"
        "# TYPE g gauge\n"
        "g 1.5\n"
        "# HELP h_seconds hist\n"
        "# TYPE h_seconds histogram\n"
        'h_seconds_bucket{le="0.1"} 1\n'
        'h_seconds_bucket{le="1"} 2\n'
        'h_seconds_bucket{le="+Inf"} 3\n'
        "h_seconds_sum 5.55\n"
        "h_seconds_count 3\n"
    )


def test_snapshot_and_json_roundtrip(tmp_path):
    r = MetricsRegistry()
    r.counter("c_total").inc(2)
    r.histogram("h", buckets=(1.0,)).observe(0.5)
    p = tmp_path / "m.json"
    r.save_json(str(p))
    data = json.loads(p.read_text())
    assert data["metrics"]["c_total"]["values"]["{}"] == 2
    assert data["metrics"]["h"]["values"]["{}"]["counts"] == [1, 0]


# ---------------------------------------------------------------------------
# ProbeView — the legacy PROBE dict shim
# ---------------------------------------------------------------------------

def test_probe_view_preserves_dict_surface():
    r = MetricsRegistry()
    pv = ProbeView(r, ("hits", "misses"))
    pv["hits"] += 2
    pv["hits"] += 1
    pv["misses"] += 5
    assert pv["hits"] == 3 and pv["misses"] == 5
    assert pv.copy() == {"hits": 3, "misses": 5}
    assert set(pv) == {"hits", "misses"} and len(pv) == 2
    assert "hits" in pv and "absent" not in pv
    # the actual storage is registry counters
    assert r.get("probe_hits").value == 3
    with pytest.raises(KeyError):
        pv["absent"]
    with pytest.raises(KeyError):
        pv["absent"] = 1
    with pytest.raises(ValueError):
        pv["hits"] = 0       # counters are monotone between resets
    pv.reset()
    assert pv["hits"] == 0 and pv["misses"] == 0
    pv["hits"] += 1
    assert pv["hits"] == 1


def test_global_probe_shim_and_reset():
    stream_scheduler.reset_probe()
    PROBE = stream_scheduler.PROBE
    assert set(PROBE.keys()) == {
        "delta_decodes", "storage_updates", "stats_refreshes",
        "seed_listings", "host_materializations", "cache_hits",
        "cache_misses", "invalidated_parts",
    }
    PROBE["cache_hits"] += 7
    assert PROBE["cache_hits"] == 7
    stream_scheduler.reset_probe()
    assert all(v == 0 for v in PROBE.values())


def test_two_services_keep_isolated_registries():
    """The PROBE clobbering bug: two services in one process used to
    share one global dict. Per-service registries must not cross."""
    stream_scheduler.reset_probe()
    g = random_graph(16, 30, seed=3)
    svcs = []
    for k in range(2):
        svc = ListingService(g, m=2, backend="host",
                             scheduler=BatchScheduler(max_ops=4, min_ops=1))
        svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
        svcs.append(svc)
    _stream(svcs[0], rounds=2, d=1, a=2, seed0=11)
    svcs[0].advance()
    _stream(svcs[1], rounds=1, d=1, a=2, seed0=31)
    svcs[1].advance()
    b0 = svcs[0].obs.metrics.get("stream_batches_total").value
    b1 = svcs[1].obs.metrics.get("stream_batches_total").value
    assert b0 == len(svcs[0].metrics) and b1 == len(svcs[1].metrics)
    assert b0 != b1                       # different work → different books
    # the global shim aggregates across both services
    agg = svcs[0].obs.metrics.get("stream_delta_decodes_total").value \
        + svcs[1].obs.metrics.get("stream_delta_decodes_total").value
    assert stream_scheduler.PROBE["delta_decodes"] == agg > 0


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    sp = tr.span("anything", attr=1)
    assert sp is NULL_SPAN
    with sp as s:
        s.add("k")
        s.set(x=2)
    assert tr.roots == []


def test_tracer_nesting_counters_and_exception_safety():
    tr = Tracer(enabled=True)
    with tr.span("a", idx=0) as a:
        with tr.span("b") as b:
            b.add("k", 2)
            b.add("k")
        try:
            with tr.span("c"):
                raise RuntimeError("boom")
        except RuntimeError:
            pass
        a.add("n_ops", 4)
    assert len(tr.roots) == 1 and tr._stack == []
    root = tr.roots[0]
    assert root.skeleton() == ("a", (("b", ()), ("c", ())))
    assert root.attrs == {"idx": 0}
    assert root.counters == {"n_ops": 4.0}
    assert root.child("b").counters == {"k": 3.0}
    assert root.dur_ns >= root.child("b").dur_ns + root.child("c").dur_ns
    # parent links are consistent
    for sp in root.walk():
        for c in sp.children:
            assert c.parent_id == sp.span_id


def test_tracer_bounds_roots():
    tr = Tracer(enabled=True, max_roots=2)
    for i in range(5):
        with tr.span("r", i=i):
            pass
    assert len(tr.roots) == 2 and tr.dropped_roots == 3
    assert [r.attrs["i"] for r in tr.roots] == [3, 4]


def test_tracer_exports(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("batch", batch_index=0) as b:
        b.add("n_ops", 3)
        with tr.span("shared_delta"):
            pass
    jp = tmp_path / "t.jsonl"
    assert tr.to_jsonl(str(jp)) == 2
    recs = [json.loads(line) for line in jp.read_text().splitlines()]
    assert [r["name"] for r in recs] == ["batch", "shared_delta"]
    assert recs[1]["parent_id"] == recs[0]["span_id"]
    assert recs[0]["counters"] == {"n_ops": 3.0}

    cp = tmp_path / "t_chrome.json"
    assert tr.to_chrome_trace(str(cp)) == 2
    doc = json.loads(cp.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X" and ev["cat"] == "stream"
        assert ev["dur"] >= 0 and ev["ts"] > 0
    # the child event nests inside the parent on the timeline
    par = next(e for e in evs if e["name"] == "batch")
    kid = next(e for e in evs if e["name"] == "shared_delta")
    assert par["ts"] <= kid["ts"]
    assert kid["ts"] + kid["dur"] <= par["ts"] + par["dur"] + 1e-3
    assert par["args"]["n_ops"] == 3.0


# ---------------------------------------------------------------------------
# JaxProfiler — compile vs execute split
# ---------------------------------------------------------------------------

def _record_annotations(monkeypatch):
    """Replace ``jax.profiler.TraceAnnotation`` with a recorder; returns
    the list of ``(name, kwargs, "enter" | "exit")`` it sees."""
    import jax.profiler

    seen = []

    class Recorder:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            seen.append((self.name, self.kwargs, "enter"))

        def __exit__(self, *exc):
            seen.append((self.name, self.kwargs, "exit"))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return seen


def test_enabled_tracer_opens_one_profiler_annotation_per_span(monkeypatch):
    seen = _record_annotations(monkeypatch)
    tr = Tracer(enabled=True)
    with tr.span("batch", batch_index=7):
        with tr.span("mirror"):
            pass
        with tr.span("graph_stats"):
            pass
    with tr.span("materialize", pattern="tri"):
        pass
    assert seen == [
        ("service.batch", {"batch": 7}, "enter"),
        ("service.mirror", {"batch": 7}, "enter"),
        ("service.mirror", {"batch": 7}, "exit"),
        ("service.graph_stats", {"batch": 7}, "enter"),
        ("service.graph_stats", {"batch": 7}, "exit"),
        ("service.batch", {"batch": 7}, "exit"),
        ("service.materialize", {}, "enter"),
        ("service.materialize", {}, "exit"),
    ]


def test_disabled_tracer_opens_no_profiler_annotation(monkeypatch):
    seen = _record_annotations(monkeypatch)
    tr = Tracer(enabled=False)
    with tr.span("batch", batch_index=0):
        with tr.span("mirror"):
            pass
    g = random_graph(16, 30, seed=5)
    svc = ListingService(g, m=2, backend="host")
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    _stream(svc, rounds=2, d=1, a=2, seed0=21)
    svc.advance()
    assert seen == []


def test_storage_step_names_its_phases():
    """The served storage step's lowered program carries the named
    scopes the trace reduction splits its device time by."""
    import jax

    from repro.core import build_np_storage
    from repro.dist import jax_engine as je
    from repro.dist import sharded

    caps = je.EngineCaps(v_cap=64, deg_cap=32, e_cap=512, match_cap=2048,
                         group_cap=2048, set_cap=32, pair_cap=64)
    mesh = jax.make_mesh((jax.local_device_count(),), ("data",))
    g = random_graph(30, 75, seed=5)
    pt = sharded.stack_partitions(build_np_storage(g, mesh.size), caps)
    ush = sharded.UpdateShapes(n_add=3, n_del=3)
    step = sharded.make_storage_update_step(mesh, caps, ush, mode="delta")
    batch = np.full((3, 2), -1, np.int32)
    text = step.lower(pt, batch, batch).as_text(debug_info=True)
    for scope in ("storage/candidates", "storage/membership", "storage/patch"):
        assert scope in text


def test_profiled_step_splits_compile_from_execute():
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    prof = JaxProfiler(reg, enabled=True)
    fn = jax.jit(lambda x: x * 2 + 1)
    step = ProfiledStep("toy", fn, lambda: prof)
    x = jnp.arange(8)
    for _ in range(3):
        step(x)
    rec = prof.steps["toy"]
    assert rec.compiles == 1 and rec.calls == 3
    assert not rec.heuristic
    assert rec.compile_seconds > 0 and rec.execute_seconds > 0
    # AOT analysis of the compiled executable is recorded
    assert rec.cost is not None and rec.memory is not None
    assert rec.memory.get("output_size_in_bytes", 0) > 0
    assert reg.get("jax_compiles_total").value_for(step="toy") == 1
    assert reg.get("jax_execute_calls_total").value_for(step="toy") == 3
    assert reg.get("jax_compile_seconds_total").value_for(step="toy") \
        == pytest.approx(rec.compile_seconds)


def test_profiled_step_recompile_accumulates_under_same_name():
    """Cap fallbacks / store resizes rewrap the jitted step in a NEW
    ProfiledStep under the SAME name — compile #2 must land in the same
    StepProfile, not a fresh one."""
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    prof = JaxProfiler(reg, enabled=True)
    fn = jax.jit(lambda x: x * 2 + 1)
    s1 = ProfiledStep("toy", fn, lambda: prof)
    s1(jnp.arange(8))
    s2 = ProfiledStep("toy", fn, lambda: prof)   # the rewrap
    s2(jnp.arange(16))                           # new shape → real recompile
    rec = prof.steps["toy"]
    assert rec.compiles == 2 and rec.calls == 2
    assert reg.get("jax_compiles_total").value_for(step="toy") == 2


def test_profiled_step_heuristic_fallback_and_disable():
    import jax.numpy as jnp

    prof = JaxProfiler(MetricsRegistry(), enabled=True)
    # a plain python callable has no .lower() — AOT fails, the split
    # degrades to first-call≈compile and is flagged
    step = ProfiledStep("plain", lambda x: x + 1, lambda: prof)
    step(jnp.ones(3))
    step(jnp.ones(3))
    rec = prof.steps["plain"]
    assert rec.heuristic
    assert rec.compiles == 1 and rec.calls == 1

    # disabled profiler → pure passthrough, zero accounting
    off = JaxProfiler(None, enabled=False)
    s2 = ProfiledStep("off", lambda x: x - 1, lambda: off)
    out = s2(jnp.ones(2))
    assert float(out[0]) == 0.0 and off.steps == {}


# ---------------------------------------------------------------------------
# Observability umbrella
# ---------------------------------------------------------------------------

def test_observability_defaults_and_export(tmp_path):
    obs = Observability()
    assert not obs.tracer.enabled and obs.jaxprof.enabled
    assert Observability.full().tracer.enabled
    assert not Observability.disabled().jaxprof.enabled

    obs.metrics.counter("c_total").inc()
    out = obs.export(str(tmp_path / "a"))
    assert set(out) == {"metrics_json", "metrics_prom"}

    full = Observability.full()
    with full.tracer.span("batch"):
        pass
    out = full.export(str(tmp_path / "b"), prefix="run")
    assert set(out) == {"metrics_json", "metrics_prom",
                        "trace_jsonl", "trace_chrome"}
    for p in out.values():
        assert (tmp_path / "b").joinpath(p.split("/")[-1]).exists()


# ---------------------------------------------------------------------------
# BatchMetrics / scheduler satellite fixes
# ---------------------------------------------------------------------------

def test_throughput_is_zero_not_inf_on_zero_latency():
    bm = BatchMetrics(batch_index=0, lo=0, hi=4, n_ops=4, net_add=2,
                      net_delete=0, latency_s=0.0, patterns={})
    assert bm.throughput_ops_s == 0.0
    bm2 = BatchMetrics(batch_index=0, lo=0, hi=4, n_ops=4, net_add=2,
                       net_delete=0, latency_s=2.0, patterns={})
    assert bm2.throughput_ops_s == 2.0


def test_scheduler_drift_monitor_calibrates_then_tracks():
    s = BatchScheduler(min_ops=1, max_ops=8)
    assert s.predict_seconds(4) is None and s.drift() is None
    # constant-rate observations: after calibration the prediction
    # matches and the drift EWMA sits at 1
    for _ in range(6):
        s.observe(4, 0.1)
    assert s.predict_seconds(4) > 0
    assert s.drift() == pytest.approx(1.0, rel=0.05)
    assert s.last_predicted_s == pytest.approx(s.last_observed_s, rel=0.3)
    # a sustained 3× slowdown pulls the EWMA visibly above 1
    for _ in range(6):
        s.observe(4, 0.3)
    assert s.drift() > 1.2


# ---------------------------------------------------------------------------
# Unit-cache LRU budget
# ---------------------------------------------------------------------------

def _host_pair(seed, **budget):
    g = random_graph(20, 45, seed=seed)
    ref = ListingService(g, m=3, backend="host",
                         scheduler=BatchScheduler(max_ops=4, min_ops=1))
    cap = ListingService(g, m=3, backend="host",
                         scheduler=BatchScheduler(max_ops=4, min_ops=1),
                         **budget)
    for svc in (ref, cap):
        svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
        svc.register("sq", PATTERN_LIBRARY["q1_square"])
    return ref, cap


def test_unit_cache_entry_budget_evicts_lru_and_stays_exact():
    ref, cap = _host_pair(seed=13, cache_max_entries=2)
    cache = cap.backend.unit_cache
    for b in range(12):
        upd = sample_update(ref.projected_graph(), 2, 2, seed=400 + b)
        ref.ingest(upd)
        cap.ingest(upd)
        ref.advance()
        cap.advance()
        assert cap.counts() == ref.counts()     # eviction never changes results
        assert len(cache._lru) <= 2
    assert cache.stats.evictions > 0
    assert cache.resident_bytes >= 0
    # evictions and footprint surface in the service registry
    m = cap.obs.metrics
    assert m.get("unit_cache_evictions_total").value == cache.stats.evictions
    assert m.get("unit_cache_resident_bytes").value == cache.resident_bytes
    # the capped run re-lists more: misses strictly above the unbudgeted run
    assert m.get("unit_cache_misses_total").value \
        >= ref.obs.metrics.get("unit_cache_misses_total").value


def test_unit_cache_byte_budget_tracks_resident_bytes():
    ref, cap = _host_pair(seed=17, cache_max_bytes=1)
    cache = cap.backend.unit_cache
    for b in range(6):
        upd = sample_update(ref.projected_graph(), 2, 2, seed=900 + b)
        ref.ingest(upd)
        cap.ingest(upd)
        ref.advance()
        cap.advance()
        assert cap.counts() == ref.counts()
        # a 1-byte budget keeps at most the single most-recent entry
        assert len(cache._lru) <= 1
    assert cache.stats.evictions > 0
    assert sum(cache._entry_bytes.values()) == cache.resident_bytes


def test_unit_cache_unbudgeted_never_evicts():
    ref, _ = _host_pair(seed=19)
    for b in range(6):
        ref.ingest(sample_update(ref.projected_graph(), 2, 2, seed=50 + b))
        ref.advance()
    assert ref.backend.unit_cache.stats.evictions == 0
    assert ref.obs.metrics.get("unit_cache_evictions_total") is None


# ---------------------------------------------------------------------------
# Span skeleton over a 50-batch host stream
# ---------------------------------------------------------------------------

_NONEMPTY_SKEL = ("batch", (("shared_delta", ()), ("storage_update", ()),
                            ("maintain", ()), ("maintain", ()),
                            ("graph_stats", ()), ("sinks", ())))
_NOOP_SKEL = ("batch", (("shared_delta", ()), ("graph_stats", ()),
                        ("sinks", ())))


def _drive_50(svc, seed0=1000):
    b = 0
    while len(svc.metrics) < 50:
        svc.ingest(sample_update(svc.projected_graph(), 2, 2, seed=seed0 + b))
        b += 1
        svc.advance()
    return svc


def _check_stream_spans(svc):
    roots = svc.obs.tracer.roots
    ms = svc.metrics
    assert len(roots) == len(ms) >= 50
    for root, bm in zip(roots, ms):
        assert root.attrs["batch_index"] == bm.batch_index
        if bm.net_add + bm.net_delete:
            assert root.skeleton() == _NONEMPTY_SKEL
        else:
            # windows netting to nothing skip storage/maintain entirely
            assert root.skeleton() == _NOOP_SKEL
        assert root.counters["n_ops"] == bm.n_ops
        # the batch span covers the measured latency (plus bookkeeping)
        assert root.dur_s >= bm.latency_s * 0.9
        assert root.dur_s <= bm.latency_s + 0.5
        assert sum(c.dur_ns for c in root.children) <= root.dur_ns
    # ---- span counters reconcile with the registry deltas
    m = svc.obs.metrics
    assert m.get("stream_batches_total").value == len(ms)
    assert sum(r.counters["n_ops"] for r in roots) \
        == m.get("stream_ops_total").value == sum(bm.n_ops for bm in ms)
    n_updates = sum(1 for r in roots if r.child("storage_update"))
    assert n_updates == m.get("stream_storage_updates_total").value
    for key, metric in (("cache_hits", "unit_cache_hits_total"),
                        ("cache_misses", "unit_cache_misses_total"),
                        ("invalidated_parts",
                         "unit_cache_invalidated_parts_total")):
        inst = m.get(metric)
        span_total = sum(r.counters.get(key, 0) for r in roots)
        # registry includes register()-time cold fills outside any batch
        assert inst is not None and inst.value >= span_total
    # drift gauge populated once the cost model calibrated
    assert svc.scheduler.drift() is not None
    assert m.get("scheduler_drift_ewma") is not None
    assert m.get("stream_batch_latency_seconds").cell().count \
        == sum(1 for bm in ms if bm.latency_s > 0)


def test_host_stream_span_tree_and_registry_reconcile():
    g = random_graph(20, 45, seed=13)
    svc = ListingService(g, m=3, backend="host",
                         scheduler=BatchScheduler(max_ops=4, min_ops=1),
                         obs=Observability.full())
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    svc.register("sq", PATTERN_LIBRARY["q1_square"])
    _check_stream_spans(_drive_50(svc))


def test_host_noop_batch_has_reduced_skeleton():
    g = random_graph(16, 30, seed=5)
    svc = ListingService(g, m=2, backend="host", obs=Observability.full())
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    # an add+delete of the same absent edges nets to an empty window
    rng = np.random.default_rng(7)
    existing = set(map(tuple, svc.projected_graph().edges().tolist()))
    absent = []
    while len(absent) < 2:
        a, b = int(rng.integers(16)), int(rng.integers(16))
        e = (min(a, b), max(a, b))
        if a != b and e not in existing and e not in absent:
            absent.append(e)
    svc.ingest(GraphUpdate.make(add=absent))
    svc.ingest(GraphUpdate.make(delete=absent))
    svc.advance()
    assert [r.skeleton() for r in svc.obs.tracer.roots] == [_NOOP_SKEL]


def test_default_service_records_no_spans():
    """Tracing is off by default — zero roots, zero span overhead."""
    g = random_graph(16, 30, seed=5)
    svc = ListingService(g, m=2, backend="host")
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    _stream(svc, rounds=2, d=1, a=2, seed0=21)
    svc.advance()
    assert svc.obs.tracer.roots == []
    assert svc.obs.tracer.span("x") is NULL_SPAN
    # metrics still flow on the default (cheap) configuration
    assert svc.obs.metrics.get("stream_batches_total").value == len(svc.metrics)


# ---------------------------------------------------------------------------
# Sharded stream: spans + compile/execute split (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_stream_spans_profile_and_chrome_export(tmp_path):
    g = random_graph(20, 45, seed=13)
    svc = ListingService(g, backend="sharded",
                         scheduler=BatchScheduler(max_ops=4, min_ops=1),
                         max_add=4, max_del=4, obs=Observability.full())
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    _drive_50(svc)
    roots = svc.obs.tracer.roots
    ms = svc.metrics
    assert len(roots) == len(ms) >= 50
    skel = ("batch", (("shared_delta", ()), ("storage_update", ()),
                      ("maintain_mega", ()), ("maintain", ()), ("mirror", ()),
                      ("graph_stats", ()), ("sinks", ())))
    for root, bm in zip(roots, ms):
        if bm.net_add + bm.net_delete:
            assert root.skeleton() == skel
        else:
            assert root.skeleton() == _NOOP_SKEL
        assert root.counters["n_ops"] == bm.n_ops
        assert root.dur_s >= bm.latency_s * 0.9
    # per-batch spans sum (within tolerance) to the measured latencies
    span_total = sum(r.dur_s for r in roots)
    lat_total = sum(bm.latency_s for bm in ms)
    assert span_total >= lat_total * 0.9
    assert span_total <= lat_total * 1.5 + 1.0

    # ---- compile vs execute split populated for EVERY jitted step
    prof = svc.obs.jaxprof
    expected = {"storage_update", "maintain_mega", "list:tri",
                "init_store:tri", "unit_refresh:tri"}
    assert expected <= set(prof.steps)
    # exactly ONE maintain profile per service — no per-pattern ghosts
    assert not any(n.startswith("maintain:") for n in prof.steps)
    # …and the fused step names each pattern's phases for the trace
    hlo = svc.backend.maintain_step._compiled.as_text()
    for phase in ("refresh", "patch", "store"):
        assert f"maintain/tri/{phase}" in hlo
    m = svc.obs.metrics
    for name in expected:
        rec = prof.steps[name]
        assert rec.compiles >= 1 and rec.compile_seconds > 0
        assert rec.calls >= 1 and rec.execute_seconds > 0
        assert not rec.heuristic
        assert rec.cost is not None and rec.memory is not None
        assert m.get("jax_compiles_total").value_for(step=name) == rec.compiles
        assert m.get("jax_execute_calls_total").value_for(step=name) == rec.calls
    # steady state: executing a batch is far cheaper than compiling it
    su = prof.steps["storage_update"]
    assert su.execute_seconds / su.calls < su.compile_seconds

    # drift gauge calibrated on the sharded path too
    assert svc.scheduler.drift() is not None
    assert m.get("scheduler_drift_ewma") is not None

    # ---- the whole bundle exports; Chrome trace is Perfetto-loadable
    out = svc.obs.export(str(tmp_path), prefix="sharded")
    doc = json.loads(open(out["trace_chrome"]).read())
    evs = doc["traceEvents"]
    assert len(evs) == sum(1 for r in roots for _ in r.walk())
    assert {e["name"] for e in evs} >= {"batch", "shared_delta",
                                        "storage_update", "maintain", "sinks"}
    assert all(e["ph"] == "X" for e in evs)
    prof_doc = json.loads(open(out["jaxprof_json"]).read())
    assert set(prof_doc["steps"]) == set(prof.steps)


@pytest.mark.slow
def test_sharded_store_resize_recompile_lands_in_same_profile():
    """A store resize recompiles the fused megastep mid-batch; the
    second compile must accumulate into the same ``maintain_mega``
    StepProfile (same step name, no per-pattern ghost entries)."""
    g = random_graph(18, 35, seed=61)
    svc = ListingService(g, backend="sharded",
                         scheduler=BatchScheduler(min_ops=1, max_ops=8),
                         max_add=4, max_del=4)
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    be = svc.backend
    orig = be.maintain_step

    def overflowing_step(pt2, stores, carries, dirty, add, dele):
        stores2, patches, carries2, diag = orig(pt2, stores, carries,
                                                dirty, add, dele)
        d = dict(diag["tri"])
        d["overflow"] = d["overflow"] + 3
        d["store_overflow"] = d["store_overflow"] + 3
        return stores2, patches, carries2, {**diag, "tri": d}

    be.maintain_step = overflowing_step
    _stream(svc, rounds=1, d=2, a=2, seed0=63)
    svc.advance()
    assert be.store_resizes == 1
    rec = svc.obs.jaxprof.steps["maintain_mega"]
    assert rec.compiles == 2                      # initial + post-resize
    assert rec.calls >= 2                         # overflowing try + retry
    hlo = be.maintain_step._compiled.as_text()    # the recompile keeps
    for phase in ("refresh", "patch", "store"):   # the phases' scopes
        assert f"maintain/tri/{phase}" in hlo
    assert not any(n.startswith("maintain:")
                   for n in svc.obs.jaxprof.steps)
    assert svc.obs.metrics.get("jax_compiles_total") \
              .value_for(step="maintain_mega") == 2
    assert all(svc.audit().values())
