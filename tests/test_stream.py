"""repro.stream: journal semantics, shared-delta sharing, service parity.

The streaming contract under test: at every committed watermark the
service's match sets **byte-match** a from-scratch ``DDSL.initial()`` on
the graph obtained by replaying the journal to that watermark — for the
host backend, the single-device sharded backend, and any micro-batch
split the scheduler chooses. Shared-delta sharing is asserted through
the :data:`repro.stream.scheduler.PROBE` counters, not trusted.
"""

import dataclasses

import numpy as np
import pytest

from conftest import random_graph

from repro.core import DDSL, Graph, GraphUpdate
from repro.core.graph import decode_edges
from repro.core.pattern import PATTERN_LIBRARY
from repro.data.graphs import sample_update
from repro.stream import (
    BatchScheduler,
    CountDeltaSink,
    ListingService,
    MatchDeltaSink,
    UpdateJournal,
)
from repro.stream import scheduler as stream_scheduler

try:  # hypothesis fuzzing runs where available (CI); deterministic
    # twins of both property tests below always run.
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


def _rows(table: np.ndarray) -> set:
    return set(map(tuple, np.asarray(table).tolist()))


def _stream(svc, rounds, d, a, seed0=0):
    """Ingest `rounds` sampled updates; returns the per-round tail marks."""
    marks = []
    for b in range(rounds):
        upd = sample_update(svc.projected_graph(), d, a, seed=seed0 + b)
        marks.append(svc.ingest(upd))
    return marks


# ---------------------------------------------------------------------------
# Journal
# ---------------------------------------------------------------------------

def test_journal_nets_insert_then_delete_to_nothing():
    j = UpdateJournal()
    j.append_edges(add=[(1, 2)])
    j.append_edges(delete=[(1, 2)])
    net = j.window(0)
    assert net.add.shape[0] == 0 and net.delete.shape[0] == 0


def test_journal_nets_delete_then_reinsert_to_nothing():
    j = UpdateJournal()
    j.append_edges(delete=[(3, 4)])
    j.append_edges(add=[(3, 4)])
    net = j.window(0)
    assert net.size == 0


def test_journal_odd_touches_net_to_first_kind():
    j = UpdateJournal()
    j.append_edges(add=[(1, 2)])
    j.append_edges(delete=[(1, 2)])
    j.append_edges(add=[(1, 2)])
    net = j.window(0)
    assert _rows(net.add) == {(1, 2)} and net.delete.shape[0] == 0


def test_journal_windows_and_watermarks():
    j = UpdateJournal()
    w1 = j.append_edges(add=[(0, 1), (2, 3)])
    w2 = j.append_edges(delete=[(0, 1)])
    assert (w1, w2) == (2, 3) and j.tail == 3
    assert j.pending(0) == 3 and j.pending(w1) == 1
    # Split windows compose to the same net as the full window.
    net_a, net_b = j.window(0, w1), j.window(w1, w2)
    assert _rows(net_a.add) == {(0, 1), (2, 3)} and _rows(net_b.delete) == {(0, 1)}
    full = j.window(0)
    assert _rows(full.add) == {(2, 3)} and full.delete.shape[0] == 0


def test_journal_truncate_bounds_replay():
    j = UpdateJournal()
    j.append_edges(add=[(0, 1)])
    j.append_edges(add=[(1, 2)])
    dropped = j.truncate(1)
    assert dropped == 1 and j.base == 1 and len(j) == 1
    assert _rows(j.replay(1).add) == {(1, 2)}
    with pytest.raises(ValueError):
        j.window(0)


class _Clock:
    """A ``time`` stand-in whose ``perf_counter`` reads a settable value."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def perf_counter(self) -> float:
        return self.t


def test_journal_stamps_give_each_batch_its_ops_wait(monkeypatch):
    """Two appends split over two batches: each op waits from its own
    append to its batch's start, and a truncate keeps the stamps of the
    ops it leaves."""
    from repro.stream import journal as journal_mod
    from repro.stream import service as service_mod

    clock = _Clock()
    monkeypatch.setattr(journal_mod, "time", clock)
    monkeypatch.setattr(service_mod, "time", clock)
    g = Graph.from_edges(np.array([[0, 1], [1, 2], [2, 3]]), n=8)
    svc = ListingService(g, m=2, backend="host",
                         scheduler=BatchScheduler(min_ops=3, max_ops=3))
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    clock.t = 10.0
    svc.ingest(delete=[(0, 1)], add=[(4, 5)])                 # seqs 1, 2
    clock.t = 13.0
    svc.ingest(add=[(0, 2), (0, 3), (5, 6)])                  # seqs 3-5
    clock.t = 20.0
    (b1,) = svc.advance(3)
    assert (b1.lo, b1.hi) == (0, 3)
    assert b1.wait_sum_s == 10.0 + 10.0 + 7.0 and b1.wait_max_s == 10.0
    gauge = svc.obs.metrics.get("stream_oldest_pending_age_seconds")
    assert gauge.value == 7.0                                 # seq 4, at 13
    assert svc.compact() == 3
    clock.t = 25.0
    (b2,) = svc.advance()
    assert (b2.lo, b2.hi) == (3, 5)
    assert b2.wait_sum_s == 12.0 + 12.0 and b2.wait_max_s == 12.0
    assert gauge.value == 0.0                                 # nothing pending


def test_journal_truncate_drops_stamps_with_their_ops(monkeypatch, tmp_path):
    from repro.stream import journal as journal_mod

    clock = _Clock(1.0)
    monkeypatch.setattr(journal_mod, "time", clock)
    j = UpdateJournal()
    j.append_edges(add=[(0, 1), (1, 2)])                      # seqs 1, 2 at 1
    clock.t = 4.0
    j.append_edges(add=[(2, 3)])                              # seq 3 at 4
    assert j.waits(0, 3, 6.0) == (5.0 + 5.0 + 2.0, 5.0)
    j.truncate(1)
    assert j.waits(1, 3, 6.0) == (5.0 + 2.0, 5.0)
    assert j.oldest_pending_age(1, 6.0) == 5.0
    j.truncate(3)
    assert j.waits(3, 3, 6.0) == (0.0, 0.0)
    assert j.oldest_pending_age(3, 6.0) == 0.0
    clock.t = 8.0
    j.append_edges(add=[(3, 4)])                              # seq 4 at 8
    assert j.waits(3, 4, 9.5) == (1.5, 1.5)
    # A loaded journal's ops carry no stamps: they add no wait.
    j.save(str(tmp_path / "j.jsonl"))
    loaded = UpdateJournal.load(str(tmp_path / "j.jsonl"))
    assert loaded.waits(3, 4, 9.5) == (0.0, 0.0)
    assert loaded.oldest_pending_age(3, 9.5) == 0.0


def _check_replay_matches_sequential(ops, lo_frac, hi_frac):
    """Netted replay of any window == applying the raw ops one by one."""
    g0 = random_graph(12, 18, seed=5)
    j = UpdateJournal()
    cur = {int(c) for c in g0.codes}
    states = [set(cur)]          # edge-code state after each op
    applied = 0
    for a, b in ops:
        if a == b:
            continue
        code = (min(a, b) << 32) | max(a, b)
        if code in cur:
            j.append_edges(delete=[(a, b)])
            cur.discard(code)
        else:
            j.append_edges(add=[(a, b)])
            cur.add(code)
        applied += 1
        states.append(set(cur))
    lo = int(round(lo_frac * applied))
    hi = lo + int(round(hi_frac * (applied - lo)))
    net = j.window(lo, hi)
    start, end = states[lo], states[hi]
    g_lo = Graph._from_codes(12, np.array(sorted(start), np.int64))
    g_hi = g_lo.apply_update(net)
    assert {int(c) for c in g_hi.codes} == end


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_journal_replay_matches_sequential_apply(seed):
    rng = np.random.default_rng(seed)
    ops = [(int(rng.integers(12)), int(rng.integers(12))) for _ in range(30)]
    _check_replay_matches_sequential(ops, float(rng.random()), float(rng.random()))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.lists(st.tuples(st.integers(0, 11), st.integers(0, 11)),
                    min_size=0, max_size=30),
           st.floats(0, 1), st.floats(0, 1))
    def test_journal_replay_matches_sequential_apply_fuzz(ops, lo_frac, hi_frac):
        _check_replay_matches_sequential(ops, lo_frac, hi_frac)


# ---------------------------------------------------------------------------
# Shared delta: computed once per batch, no matter how many patterns
# ---------------------------------------------------------------------------

def test_shared_delta_decoded_once_per_batch_two_patterns():
    g = random_graph(28, 70, seed=2)
    svc = ListingService(g, m=4, backend="host",
                         scheduler=BatchScheduler(max_ops=6))
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    svc.register("sq", PATTERN_LIBRARY["q1_square"])
    _stream(svc, rounds=4, d=3, a=3, seed0=11)
    pending = svc.journal.pending(0)
    stream_scheduler.reset_probe()
    svc.advance()
    n_batches = len(svc.metrics)
    assert n_batches >= 2, "scheduler must have split the stream"
    probe = stream_scheduler.PROBE
    # One decode + one Φ(d') update + one stats refresh per batch —
    # NOT per (batch × pattern).
    assert probe["delta_decodes"] == n_batches
    assert probe["storage_updates"] == n_batches
    assert probe["stats_refreshes"] == n_batches
    assert sum(m.n_ops for m in svc.metrics) == pending


def test_shared_seed_listings_are_cached_across_patterns():
    g = random_graph(28, 70, seed=3)
    svc = ListingService(g, m=4, backend="host",
                         scheduler=BatchScheduler(max_ops=100))
    # The same pattern twice: every per-unit seed listing is shareable.
    svc.register("tri_a", PATTERN_LIBRARY["q2_triangle"])
    svc.register("tri_b", PATTERN_LIBRARY["q2_triangle"])
    _stream(svc, rounds=1, d=4, a=4, seed0=21)
    stream_scheduler.reset_probe()
    svc.advance()
    assert len(svc.metrics) == 1
    n_units = len(svc.backend.meta("tri_a").units)
    assert stream_scheduler.PROBE["seed_listings"] == n_units  # not 2 × n_units
    assert svc.count("tri_a") == svc.count("tri_b")


# ---------------------------------------------------------------------------
# Service parity vs. from-scratch listing
# ---------------------------------------------------------------------------

def _assert_byte_match(svc, specs):
    for name, pattern in specs:
        fresh = DDSL(svc.graph, pattern, m=4)
        fresh.initial()
        assert fresh.count() == svc.count(name)
        assert _rows(fresh.matches_plain()) == _rows(svc.backend.matches_plain(name))


def _check_stream_byte_match(seed0, rounds):
    """Random streams: at every committed watermark, journal replay and
    the service's tables byte-match a from-scratch DDSL.initial()."""
    g = random_graph(20, 40, seed=7)
    svc = ListingService(g, m=3, backend="host",
                         scheduler=BatchScheduler(max_ops=5))
    specs = [("tri", PATTERN_LIBRARY["q2_triangle"]),
             ("sq", PATTERN_LIBRARY["q1_square"])]
    for name, pat in specs:
        svc.register(name, pat)
    for b in range(rounds):
        upd = sample_update(svc.projected_graph(), 2, 2, seed=seed0 + b)
        svc.ingest(upd)
        svc.advance()
        # journal replay to the committed watermark == committed graph
        replayed = Graph._from_codes(
            max(g.n, svc.graph.n), g.apply_update(
                svc.journal.replay(0, svc.committed_watermark)).codes)
        assert {int(c) for c in replayed.codes} == {int(c) for c in svc.graph.codes}
        _assert_byte_match(svc, specs)


@pytest.mark.parametrize("seed0,rounds", [(100, 3), (4242, 2), (77, 4)])
def test_random_stream_counts_byte_match_scratch(seed0, rounds):
    _check_stream_byte_match(seed0, rounds)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(0, 10_000), st.integers(1, 4))
    def test_hypothesis_stream_counts_byte_match_scratch(seed0, rounds):
        _check_stream_byte_match(seed0, rounds)


def test_multi_pattern_shared_delta_parity():
    """Three patterns over one journal advance together and all stay exact."""
    g = random_graph(26, 60, seed=9)
    svc = ListingService(g, m=4, backend="host",
                         scheduler=BatchScheduler(max_ops=7), audit_every=2)
    specs = [("tri", PATTERN_LIBRARY["q2_triangle"]),
             ("sq", PATTERN_LIBRARY["q1_square"]),
             ("house", PATTERN_LIBRARY["q5_house"])]
    for name, pat in specs:
        svc.register(name, pat)
    _stream(svc, rounds=5, d=3, a=3, seed0=31)
    svc.advance()
    assert len(svc.metrics) >= 2
    _assert_byte_match(svc, specs)
    assert svc.audits and all(ok for _, _, ok in svc.audits)


def test_50_batch_stream_host_backend_counts_match_scratch():
    """Acceptance: 50 micro-batches, 2 patterns, host backend."""
    g = random_graph(20, 45, seed=13)
    svc = ListingService(g, m=3, backend="host",
                         scheduler=BatchScheduler(max_ops=4, min_ops=1))
    specs = [("tri", PATTERN_LIBRARY["q2_triangle"]),
             ("sq", PATTERN_LIBRARY["q1_square"])]
    for name, pat in specs:
        svc.register(name, pat)
    stream_scheduler.reset_probe()
    batches = 0
    b = 0
    while batches < 50:
        upd = sample_update(svc.projected_graph(), 2, 2, seed=1000 + b)
        svc.ingest(upd)
        batches += len(svc.advance())
        b += 1
    assert len(svc.metrics) >= 50
    # Φ updates once per batch with a net effect; no-op windows skip it.
    nonempty = sum(1 for m in svc.metrics if m.net_add + m.net_delete)
    assert stream_scheduler.PROBE["storage_updates"] == nonempty
    _assert_byte_match(svc, specs)


@pytest.mark.slow
def test_50_batch_stream_sharded_backend_counts_match_scratch():
    """Acceptance: 50 micro-batches, 2 patterns, single-device sharded
    backend sharing one device storage step; overflow stays zero."""
    g = random_graph(20, 45, seed=13)
    svc = ListingService(g, backend="sharded",
                         scheduler=BatchScheduler(max_ops=4, min_ops=1),
                         max_add=4, max_del=4)
    specs = [("tri", PATTERN_LIBRARY["q2_triangle"]),
             ("sq", PATTERN_LIBRARY["q1_square"])]
    for name, pat in specs:
        svc.register(name, pat)
    batches = 0
    b = 0
    while batches < 50:
        upd = sample_update(svc.projected_graph(), 2, 2, seed=2000 + b)
        svc.ingest(upd)
        batches += len(svc.advance())
        b += 1
    assert len(svc.metrics) >= 50
    assert all(bm.overflow == 0 for bm in svc.metrics)
    # candidate counters are per-batch (delta-bounded), never cumulative
    dcap = svc.backend.caps.deg_cap
    for bm in svc.metrics:
        net = bm.net_add + bm.net_delete
        if net:
            assert 0 < bm.cand_vertices <= 2 * net * (dcap + 1)
            assert 0 < bm.cand_edges <= 2 * net * dcap
        else:
            assert bm.cand_vertices == -1 and bm.cand_edges == -1
    _assert_byte_match(svc, specs)


# ---------------------------------------------------------------------------
# No-op windows: adds/deletes netting to nothing move only the watermark
# ---------------------------------------------------------------------------

def _absent_edges(graph, k, seed=0):
    rng = np.random.default_rng(seed)
    existing = set(map(tuple, graph.edges().tolist()))
    out = set()
    while len(out) < k:
        a, b = int(rng.integers(graph.n)), int(rng.integers(graph.n))
        if a != b and (min(a, b), max(a, b)) not in existing:
            out.add((min(a, b), max(a, b)))
    return sorted(out)


def _check_noop_window(svc, k=2, seed=5):
    edges = _absent_edges(svc.projected_graph(), k, seed=seed)
    svc.ingest(GraphUpdate.make(add=edges))
    svc.ingest(GraphUpdate.make(delete=edges))
    before = dict(svc.counts())
    stream_scheduler.reset_probe()
    svc.advance()
    bm = svc.metrics[-1]
    assert svc.committed_watermark == svc.journal.tail
    assert stream_scheduler.PROBE["storage_updates"] == 0
    assert stream_scheduler.PROBE["delta_decodes"] >= 1
    assert bm.net_add == 0 and bm.net_delete == 0
    assert bm.cand_vertices == -1 and bm.storage_overflow == 0
    assert svc.counts() == before
    for rep in bm.patterns.values():
        assert rep.count_before == rep.count_after
    assert all(svc.audit().values())


def test_noop_window_host_backend():
    g = random_graph(20, 40, seed=31)
    svc = ListingService(g, m=3, backend="host",
                         scheduler=BatchScheduler(min_ops=4, max_ops=64))
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    _check_noop_window(svc, k=2, seed=5)


def test_noop_window_sharded_backend():
    g = random_graph(18, 35, seed=37)
    svc = ListingService(g, backend="sharded",
                         scheduler=BatchScheduler(min_ops=4, max_ops=64),
                         max_add=4, max_del=4)
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    _check_noop_window(svc, k=2, seed=7)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(0, 10_000), st.integers(1, 4))
    def test_hypothesis_noop_windows_keep_watermark_parity(seed, k):
        g = random_graph(14, 25, seed=9)
        svc = ListingService(g, m=2, backend="host",
                             scheduler=BatchScheduler(min_ops=2 * k, max_ops=64))
        svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
        _check_noop_window(svc, k=k, seed=seed)


# ---------------------------------------------------------------------------
# Journal truncation at the committed watermark
# ---------------------------------------------------------------------------

def _toggle_ops(journal, graph, ops):
    """Apply toggle ops (delete-if-present / add-if-absent) to a journal."""
    cur = {int(c) for c in graph.codes}
    for a, b in ops:
        if a == b:
            continue
        code = (min(a, b) << 32) | max(a, b)
        if code in cur:
            journal.append_edges(delete=[(a, b)])
            cur.discard(code)
        else:
            journal.append_edges(add=[(a, b)])
            cur.add(code)


def _check_truncate_at_watermark(ops, w_frac):
    """Truncating at a watermark must leave every later window's netting
    (and appended continuation) identical to an untruncated twin."""
    g = random_graph(12, 18, seed=5)
    full = UpdateJournal()
    cut = UpdateJournal()
    _toggle_ops(full, g, ops)
    _toggle_ops(cut, g, ops)
    w = int(round(w_frac * full.tail))
    dropped = cut.truncate(w)
    assert dropped == w and cut.base == w
    assert len(cut) == full.tail - w
    # continuation: both journals keep ingesting the same stream
    for j in (full, cut):
        j.append_edges(add=[(100, 101)])
        j.append_edges(delete=[(100, 101)])
    assert full.tail == cut.tail
    for hi in range(w, full.tail + 1):
        net_f = full.window(w, hi)
        net_c = cut.window(w, hi)
        assert _rows(net_f.add) == _rows(net_c.add)
        assert _rows(net_f.delete) == _rows(net_c.delete)
    assert full.pending(w) == cut.pending(w)
    assert [e.seq for e in cut.entries(w)] == [e.seq for e in full.entries(w)]
    # truncating again at (or below) the same watermark is a no-op
    assert cut.truncate(w) == 0
    # replay below the truncation point is refused, not silently wrong
    if w > 0:
        with pytest.raises(ValueError):
            cut.window(w - 1)


@pytest.mark.parametrize("w_frac", [0.0, 0.33, 0.5, 1.0])
def test_journal_truncate_at_watermark_replay_parity(w_frac):
    rng = np.random.default_rng(11)
    ops = [(int(rng.integers(12)), int(rng.integers(12))) for _ in range(24)]
    _check_truncate_at_watermark(ops, w_frac)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.lists(st.tuples(st.integers(0, 11), st.integers(0, 11)),
                    min_size=1, max_size=24),
           st.floats(0, 1))
    def test_journal_truncate_replay_parity_fuzz(ops, w_frac):
        _check_truncate_at_watermark(ops, w_frac)


def _check_save_load_replay_parity(ops, w_frac, tmp_path):
    """A saved+loaded journal is indistinguishable from its in-memory
    twin: same watermarks, same netting of every window, same
    continuation after further ingests."""
    g = random_graph(12, 18, seed=5)
    mem = UpdateJournal()
    _toggle_ops(mem, g, ops)
    w = int(round(w_frac * mem.tail))
    mem.truncate(w)
    path = str(tmp_path / "journal.jsonl")
    mem.save(path)
    disk = UpdateJournal.load(path)
    assert (disk.base, disk.tail, len(disk)) == (mem.base, mem.tail, len(mem))
    for j in (mem, disk):
        j.append_edges(add=[(100, 101)])
    for hi in range(mem.base, mem.tail + 1):
        net_m = mem.window(mem.base, hi)
        net_d = disk.window(disk.base, hi)
        assert _rows(net_m.add) == _rows(net_d.add)
        assert _rows(net_m.delete) == _rows(net_d.delete)
    assert [dataclasses.astuple(e) for e in disk.entries(disk.base)] == \
           [dataclasses.astuple(e) for e in mem.entries(mem.base)]


@pytest.mark.parametrize("seed,w_frac", [(0, 0.0), (1, 0.4), (2, 1.0)])
def test_journal_save_load_replay_parity(seed, w_frac, tmp_path):
    rng = np.random.default_rng(seed)
    ops = [(int(rng.integers(12)), int(rng.integers(12))) for _ in range(20)]
    _check_save_load_replay_parity(ops, w_frac, tmp_path)


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.lists(st.tuples(st.integers(0, 11), st.integers(0, 11)),
                    min_size=1, max_size=20),
           st.floats(0, 1))
    def test_journal_save_load_replay_parity_fuzz(ops, w_frac):
        import tempfile
        from pathlib import Path

        with tempfile.TemporaryDirectory() as d:
            _check_save_load_replay_parity(ops, w_frac, Path(d))


def test_journal_load_rejects_corruption(tmp_path):
    j = UpdateJournal()
    j.append_edges(add=[(0, 1), (1, 2)])
    path = str(tmp_path / "journal.jsonl")
    j.save(path)
    # not a journal
    other = tmp_path / "other.jsonl"
    other.write_text('{"kind": "something-else"}\n')
    with pytest.raises(ValueError):
        UpdateJournal.load(str(other))
    # a torn tail (crashed writer) leaves a sequence gap vs the header
    lines = open(path).read().splitlines()
    (tmp_path / "torn.jsonl").write_text("\n".join(lines[:-1]) + "\n")
    with pytest.raises(ValueError):
        UpdateJournal.load(str(tmp_path / "torn.jsonl"))
    # a bad op kind is refused
    bad = lines[:1] + [lines[1].replace('"op": 1', '"op": 7')] + lines[2:]
    (tmp_path / "bad.jsonl").write_text("\n".join(bad) + "\n")
    with pytest.raises(ValueError):
        UpdateJournal.load(str(tmp_path / "bad.jsonl"))
    # a future format revision fails fast instead of mis-parsing
    fut = [lines[0].replace('"version": 1', '"version": 2')] + lines[1:]
    (tmp_path / "future.jsonl").write_text("\n".join(fut) + "\n")
    with pytest.raises(ValueError, match="version"):
        UpdateJournal.load(str(tmp_path / "future.jsonl"))


def test_journal_truncate_at_tail_then_window_is_empty():
    j = UpdateJournal()
    j.append_edges(add=[(0, 1), (1, 2)])
    assert j.truncate(j.tail) == 2
    assert len(j) == 0 and j.base == j.tail
    net = j.window(j.tail)
    assert net.size == 0
    # appends continue the sequence numbering seamlessly
    assert j.append_edges(add=[(2, 3)]) == 3
    assert _rows(j.window(2).add) == {(2, 3)}


# ---------------------------------------------------------------------------
# Seed-table memo keying (shared-delta correctness oracle)
# ---------------------------------------------------------------------------

def test_seed_provider_key_distinguishes_anchor_and_ord():
    """Two patterns sharing a unit shape but differing in anchor or in
    the ord restriction must each get their own seed table — every
    cached result is checked against a direct listing oracle."""
    from repro.core.match_engine import list_matches
    from repro.core.pattern import Pattern, R1Unit
    from repro.core.storage import build_np_storage
    from repro.core.vcbc import compress_table
    from repro.stream.scheduler import compute_shared_delta

    g = random_graph(20, 45, seed=3)
    storage = build_np_storage(g, 3)
    j = UpdateJournal()
    j.append_edges(add=_absent_edges(g, 2, seed=4))
    delta = compute_shared_delta(j, 0, j.tail)
    delta.ensure_storage(storage)

    tri = Pattern.make([(0, 1), (0, 2), (1, 2)])
    unit = R1Unit(pattern=tri, anchors=(0, 1, 2))
    cases = [
        ((0, 1), ((1, 2),)),   # anchor 0, ord {1<2}
        ((0, 1), ()),          # anchor 0, no ord — must NOT reuse case 1
        ((1, 2), ((1, 2),)),   # anchor 1 — must NOT reuse case 1
        ((0, 1), ((0, 1), (1, 2))),
    ]
    for cover, ord_ in cases:
        got = delta.seed_provider(cover, ord_)(unit)
        anchor = unit.anchor_in(tuple(sorted(cover)))
        pieces = []
        cols = None
        for part in delta.storage.parts:
            cols, t = list_matches(part, tri, ord_, anchor=anchor,
                                   anchor_to_centers=True,
                                   require_edge_codes=delta.add_codes)
            pieces.append(t)
        table = np.concatenate(pieces, axis=0)
        want = compress_table(tri, tuple(sorted(cover)), cols, table)
        assert _rows(got.decompress(ord_)[1]) == _rows(want.decompress(ord_)[1])


def test_seed_provider_key_is_order_canonical():
    """Ord pairs in a different order are the same restriction — the
    memo must share (one listing, not two)."""
    from repro.core.pattern import Pattern, R1Unit
    from repro.core.storage import build_np_storage
    from repro.stream.scheduler import compute_shared_delta

    g = random_graph(20, 45, seed=6)
    storage = build_np_storage(g, 3)
    j = UpdateJournal()
    j.append_edges(add=_absent_edges(g, 2, seed=8))
    delta = compute_shared_delta(j, 0, j.tail)
    delta.ensure_storage(storage)

    tri = Pattern.make([(0, 1), (0, 2), (1, 2)])
    unit = R1Unit(pattern=tri, anchors=(0, 1, 2))
    stream_scheduler.reset_probe()
    a = delta.seed_provider((0, 1), ((0, 1), (1, 2)))(unit)
    b = delta.seed_provider((0, 1), ((1, 2), (0, 1)))(unit)
    assert stream_scheduler.PROBE["seed_listings"] == 1
    assert _rows(a.decompress(((0, 1), (1, 2)))[1]) == _rows(
        b.decompress(((0, 1), (1, 2)))[1])


# ---------------------------------------------------------------------------
# Sinks, metrics, scheduler behavior
# ---------------------------------------------------------------------------

def test_sinks_receive_consistent_deltas():
    g = random_graph(24, 55, seed=15)
    svc = ListingService(g, m=4, backend="host",
                         scheduler=BatchScheduler(max_ops=5))
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    counts = svc.subscribe(CountDeltaSink())
    deltas = svc.subscribe(MatchDeltaSink(patterns=["tri"]))
    before = svc.count("tri")
    before_rows = _rows(svc.backend.matches_plain("tri"))
    _stream(svc, rounds=3, d=3, a=3, seed0=41)
    svc.advance()
    # count deltas telescope to the final count
    assert before + counts.totals.get("tri", 0) == svc.count("tri")
    # row deltas replay (in batch order: removes, then adds) to the
    # final match set
    rows = set(before_rows)
    by_hi: dict = {}
    for _, hi, r in deltas.removed:
        by_hi.setdefault(hi, [set(), set()])[0] |= _rows(r)
    for _, hi, r in deltas.added:
        by_hi.setdefault(hi, [set(), set()])[1] |= _rows(r)
    for hi in sorted(by_hi):
        rem, add = by_hi[hi]
        rows -= rem
        rows |= add
    assert rows == _rows(svc.backend.matches_plain("tri"))


def test_ingest_validates_against_projected_graph():
    g = random_graph(12, 20, seed=17)
    svc = ListingService(g, m=2, backend="host")
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    e = tuple(int(x) for x in g.edges()[0])
    with pytest.raises(ValueError):
        svc.ingest(GraphUpdate.make(add=[e]))        # already present
    svc.ingest(GraphUpdate.make(delete=[e]))         # pending delete...
    with pytest.raises(ValueError):
        svc.ingest(GraphUpdate.make(delete=[e]))     # ...can't delete twice
    svc.ingest(GraphUpdate.make(add=[e]))            # re-insert pending is fine
    svc.advance()
    assert svc.audit()["tri"]


def test_scheduler_adapts_batch_size():
    sch = BatchScheduler(target_cost=100.0, target_latency_s=0.010,
                         min_ops=1, max_ops=64)
    tri = PATTERN_LIBRARY["q2_triangle"]
    from repro.core import GraphStats, symmetry_break
    from repro.core.join_tree import minimum_unit_decomposition

    g = random_graph(24, 55, seed=19)
    sch.register("tri", tri, symmetry_break(tri),
                 minimum_unit_decomposition(tri, (0, 1)))
    sch.refresh(GraphStats.of(g))
    k0 = sch.next_batch_size(1_000)
    assert 1 <= k0 <= 64
    # Slow observations shrink the batch; fast ones grow it back.
    sch.observe(k0, elapsed_s=10.0)
    assert sch.next_batch_size(1_000) == 1
    for _ in range(40):
        sch.observe(64, elapsed_s=1e-4)
    assert sch.next_batch_size(1_000) > 1


def test_scheduler_degenerate_bounds_clamp():
    """0/negative bounds or a zero budget must clamp into [1, max_ops],
    never collapse the batch size to 0 (which would spin advance())."""
    sch = BatchScheduler(target_cost=0.0, min_ops=0, max_ops=0)
    assert sch.min_ops == 1 and sch.max_ops == 1
    assert sch.next_batch_size(100) == 1
    assert sch.next_batch_size(0) == 0
    sch2 = BatchScheduler(min_ops=-3, max_ops=-7)
    assert sch2.next_batch_size(50) >= 1
    sch2.clamp_max_ops(0)
    assert sch2.max_ops == 1 and sch2.min_ops == 1


def test_scheduler_empty_graph_estimates_stay_bounded():
    from repro.core import Graph, GraphStats, symmetry_break
    from repro.core.join_tree import minimum_unit_decomposition

    tri = PATTERN_LIBRARY["q2_triangle"]
    sch = BatchScheduler(target_cost=1000.0, min_ops=1, max_ops=32)
    sch.register("tri", tri, symmetry_break(tri),
                 minimum_unit_decomposition(tri, (0, 1)))
    empty = Graph.from_edges(np.empty((0, 2), np.int64), n=0)
    sch.refresh(GraphStats.of(empty))   # zero per-op estimates
    k = sch.next_batch_size(1_000)
    assert 1 <= k <= 32


def test_scheduler_cold_start_ewma_ignores_zero_latency():
    """Batches below clock resolution must not seed (or dilute) the
    latency EWMA — the first *measurable* batch sets the calibration."""
    sch = BatchScheduler(target_cost=1e9, target_latency_s=0.01,
                         min_ops=1, max_ops=1000)
    for _ in range(5):
        sch.observe(10, 0.0)            # zero-resolution clock ticks
    assert sch._sec_per_op is None      # still cold
    assert sch.next_batch_size(10_000) == 1000   # clamped, no div-by-zero
    sch.observe(10, 1.0)                # first real signal: 0.1 s/op
    assert sch._sec_per_op == pytest.approx(0.1)
    sch.observe(10, float("nan"))       # garbage clock reading ignored
    assert sch._sec_per_op == pytest.approx(0.1)
    assert sch.next_batch_size(10_000) == 1      # 0.01s target / 0.1s per op


def test_sharded_per_batch_metrics_reset_each_batch():
    """Candidate counters and overflow are per-micro-batch values, not
    running totals: a small batch after a big one reports the small
    batch's (bounded) numbers, and a no-op batch reports none."""
    g = random_graph(18, 35, seed=41)
    svc = ListingService(g, backend="sharded",
                         scheduler=BatchScheduler(min_ops=1, max_ops=64),
                         max_add=8, max_del=8)
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    dcap = svc.backend.caps.deg_cap

    upd = sample_update(svc.projected_graph(), 4, 4, seed=43)   # big batch
    svc.ingest(upd)
    svc.advance()
    big = svc.metrics[-1]
    upd = sample_update(svc.projected_graph(), 1, 1, seed=44)   # small batch
    svc.ingest(upd)
    svc.advance()
    small = svc.metrics[-1]
    assert 0 < big.cand_vertices <= 2 * 8 * (dcap + 1)
    # Were the counters cumulative, the small batch would report at
    # least the big batch's candidate set on top of its own.
    assert 0 < small.cand_vertices <= 2 * 2 * (dcap + 1)
    edges = _absent_edges(svc.projected_graph(), 2, seed=45)    # no-op batch
    svc.ingest(GraphUpdate.make(add=edges))
    svc.ingest(GraphUpdate.make(delete=edges))
    svc.advance(watermark=svc.journal.tail)
    noop = svc.metrics[-1]
    assert noop.cand_vertices == -1 and noop.cand_edges == -1
    assert noop.storage_overflow == 0 and noop.overflow == 0
    assert all(svc.audit().values())


# ---------------------------------------------------------------------------
# Device-resident match maintenance: count-only batches never leave the mesh
# ---------------------------------------------------------------------------

def test_sharded_count_only_batches_keep_matches_on_device():
    """Acceptance: with no match-row subscribers, apply_batch pulls only
    scalars — zero match-state bytes device→host, zero host
    materializations (PROBE), across a multi-batch stream."""
    g = random_graph(18, 35, seed=51)
    svc = ListingService(g, backend="sharded",
                         scheduler=BatchScheduler(min_ops=1, max_ops=8),
                         max_add=4, max_del=4)
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    svc.register("sq", PATTERN_LIBRARY["q1_square"])
    svc.subscribe(CountDeltaSink())          # counts only — no rows
    stream_scheduler.reset_probe()
    _stream(svc, rounds=4, d=2, a=2, seed0=53)
    svc.advance()
    assert len(svc.metrics) >= 2
    assert all(bm.host_bytes == 0 for bm in svc.metrics)
    assert stream_scheduler.PROBE["host_materializations"] == 0
    assert svc.backend.total_host_bytes == 0
    # audits ride on the device count reduction — still no pull
    assert all(svc.audit().values())
    assert svc.backend.total_host_bytes == 0
    # on-demand materialization is the only host path, and it is exact
    for name in ("tri", "sq"):
        fresh = DDSL(svc.graph, svc.backend.meta(name).pattern, m=4)
        fresh.initial()
        assert _rows(fresh.matches_plain()) == _rows(svc.backend.matches_plain(name))
    assert svc.backend.total_host_bytes > 0
    assert stream_scheduler.PROBE["host_materializations"] == 2


def test_sharded_match_sink_triggers_lazy_materialization():
    """A wants_matches sink makes exactly the subscribed pattern's rows
    travel: host_bytes goes positive, the deltas replay to the final
    match set, and the materialization cache is per-watermark."""
    g = random_graph(18, 35, seed=55)
    svc = ListingService(g, backend="sharded",
                         scheduler=BatchScheduler(min_ops=1, max_ops=8),
                         max_add=4, max_del=4)
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    before_rows = _rows(svc.backend.matches_plain("tri"))
    deltas = svc.subscribe(MatchDeltaSink(patterns=["tri"]))
    _stream(svc, rounds=3, d=2, a=2, seed0=57)
    svc.advance()
    nonempty = [bm for bm in svc.metrics if bm.net_add + bm.net_delete]
    assert nonempty and all(bm.host_bytes > 0 for bm in nonempty)
    rows = set(before_rows)
    by_hi: dict = {}
    for _, hi, r in deltas.removed:
        by_hi.setdefault(hi, [set(), set()])[0] |= _rows(r)
    for _, hi, r in deltas.added:
        by_hi.setdefault(hi, [set(), set()])[1] |= _rows(r)
    for hi in sorted(by_hi):
        rem, add = by_hi[hi]
        rows -= rem
        rows |= add
    assert rows == _rows(svc.backend.matches_plain("tri"))


def _doctored_maintain(be, name="tri", extra=5, store_extra=0):
    """Wrap the backend's fused megastep so one pattern's diag reports
    extra (store-)overflow — the seam every overflow-path test uses."""
    orig = be.maintain_step

    def overflowing_step(pt2, stores, carries, dirty, add, dele):
        stores2, patches, carries2, diag = orig(pt2, stores, carries,
                                                dirty, add, dele)
        d = dict(diag[name])
        d["overflow"] = d["overflow"] + extra
        d["store_overflow"] = d["store_overflow"] + store_extra
        return stores2, patches, carries2, {**diag, name: d}

    return overflowing_step


def _small_sharded_service(seed, **kw):
    g = random_graph(18, 35, seed=seed)
    svc = ListingService(g, backend="sharded",
                         scheduler=BatchScheduler(min_ops=1, max_ops=8),
                         max_add=4, max_del=4, **kw)
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    return svc


def test_sharded_strict_overflow_aborts_batch_and_stays_usable():
    """Capped device state is persistent: a maintain overflow would
    lose match groups forever. Strict mode (the fail-stop opt-in) must
    raise before committing the lossy batch — and because the fused
    megastep is atomic across patterns AND may have consumed its
    donated store/carry inputs, the abort path rebuilds the
    committed-watermark state from the never-donated partitions: the
    backend stays fully usable (the donation-safety contract)."""
    svc = _small_sharded_service(seed=61, strict_overflow=True)
    be = svc.backend
    orig = be.maintain_step
    count0 = svc.count("tri")
    be.maintain_step = _doctored_maintain(be)
    _stream(svc, rounds=1, d=2, a=2, seed0=63)
    with pytest.raises(RuntimeError, match="overflowed device caps"):
        svc.advance()
    # nothing committed; the rebuilt pre-batch state still answers
    assert svc.committed_watermark == 0
    assert svc.count("tri") == count0
    assert be.entries["tri"].store is not None
    assert all(svc.audit().values())
    assert svc.backend.matches_plain("tri").shape[1] == 3
    # un-doctored, the SAME pending batch replays over the rebuilt
    # stores/carries and the stream resumes exactly
    be.maintain_step = orig
    svc.advance()
    assert svc.committed_watermark == svc.journal.tail
    assert all(svc.audit().values())


def test_sharded_strict_storage_overflow_raises_before_commit():
    """Storage-step overflow escalates before any store moves (nothing
    committed → not poisoned; a fixed backend can retry). Pin
    never-overflow ushapes: estimator caps would fall back + retry."""
    from repro.dist import sharded as _sharded

    svc = _small_sharded_service(seed=61, strict_overflow=True)
    be = svc.backend
    be.ushapes = _sharded.UpdateShapes(n_add=4, n_del=4)
    orig_storage = be.storage_step

    def overflowing_storage(pt, add, dele):
        pt2, diag = orig_storage(pt, add, dele)
        return pt2, {**diag, "overflow": diag["overflow"] + 3}

    be.storage_step = overflowing_storage
    _stream(svc, rounds=1, d=2, a=2, seed0=63)
    with pytest.raises(RuntimeError, match="storage update overflowed"):
        svc.advance()
    # undoctored backend recovers — the batch was never committed
    be.storage_step = orig_storage
    svc.advance()
    assert svc.committed_watermark == svc.journal.tail
    assert all(svc.audit().values())


def test_sharded_best_effort_mode_downgrades_overflow_to_metric():
    """Non-store overflow (engine caps) in best-effort mode stays a
    counted metric — no resize can fix it, so none is attempted."""
    svc = _small_sharded_service(seed=61, strict_overflow=False)
    svc.backend.maintain_step = _doctored_maintain(svc.backend)
    _stream(svc, rounds=1, d=2, a=2, seed0=63)
    svc.advance()
    assert svc.metrics[-1].overflow >= 5
    assert svc.backend.store_resizes == 0
    assert svc.committed_watermark == svc.journal.tail


def test_sharded_store_overflow_auto_resizes_and_retries():
    """Store-cap overflow in best-effort mode (the default) self-heals:
    ×2 caps, stores rebuilt by re-listing over the never-donated
    partitions, megastep recompiled, same batch retried — nothing lossy
    ever commits and the stream stays exact. The recompile also sheds
    the doctored wrapper, so exactly one resize round runs."""
    svc = _small_sharded_service(seed=61)      # best-effort is the default
    be = svc.backend
    e = be.entries["tri"]
    be.maintain_step = _doctored_maintain(be, extra=3, store_extra=3)
    g0, s0 = e.store_caps.group_cap, e.store_caps.set_cap
    _stream(svc, rounds=1, d=2, a=2, seed0=63)
    svc.advance()
    # one resize: the recompiled (undoctored) step retried cleanly
    assert be.store_resizes == 1
    assert (e.store_caps.group_cap, e.store_caps.set_cap) == (2 * g0, 2 * s0)
    assert svc.metrics[-1].overflow == 0
    assert svc.committed_watermark == svc.journal.tail
    assert all(svc.audit().values())
    # the resized store keeps streaming exactly
    _stream(svc, rounds=1, d=2, a=2, seed0=64)
    svc.advance()
    assert all(svc.audit().values())


def test_estimator_cap_overflow_falls_back_and_retries():
    """A batch that outruns the estimator-sized candidate caps must not
    kill the stream: nothing is committed, the backend permanently
    falls back to the never-overflow derivation, retries the same
    batch, and stays exact."""
    from repro.dist import sharded

    g = random_graph(18, 35, seed=71)
    svc = ListingService(g, backend="sharded",
                         scheduler=BatchScheduler(min_ops=1, max_ops=8),
                         max_add=4, max_del=4)
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    # Force caps far below any real candidate set (as if the estimator
    # badly undershot a hub-heavy delta).
    be = svc.backend
    be.ushapes = sharded.UpdateShapes(n_add=4, n_del=4, cand_cap=2, cedge_cap=2)
    be.storage_step = be._sharded.make_storage_update_step(
        be.mesh, be.caps, be.ushapes, mode=be.update_mode)
    _stream(svc, rounds=2, d=2, a=2, seed0=73)
    svc.advance()
    assert be.cap_fallbacks == 1                       # one permanent fallback
    assert be.ushapes.cand_cap is None                 # never-overflow now
    assert svc.committed_watermark == svc.journal.tail
    assert all(bm.storage_overflow == 0 for bm in svc.metrics)
    assert all(svc.audit().values())


def test_update_shapes_from_estimator_clamped_and_fallback():
    """Estimator-sized candidate caps never exceed the never-overflow
    bound (they only shrink the psum payload) and degenerate stats fall
    back to the never-overflow derivation."""
    from repro.core import Graph, GraphStats
    from repro.dist import jax_engine as je
    from repro.dist.sharded import UpdateShapes

    caps = je.EngineCaps(v_cap=64, deg_cap=32, e_cap=512, match_cap=128,
                         group_cap=128, set_cap=16, pair_cap=16)
    g = random_graph(30, 70, seed=3)
    est = UpdateShapes.from_estimator(4, 4, GraphStats.of(g), caps, m=2)
    exact = UpdateShapes(n_add=4, n_del=4)
    c1, cand_e, cedge_e = est.delta_caps(caps, 2)
    _, cand_x, cedge_x = exact.delta_caps(caps, 2)
    assert est.cand_cap is not None and est.cedge_cap is not None
    assert 0 < cand_e <= cand_x and 0 < cedge_e <= cedge_x
    # a heavy-tailed histogram: size-biased mean ≪ deg_cap ⇒ real shrink
    heavy = GraphStats(n=10_000, m=5_776,
                       deg_hist=tuple([0, 9000, 900, 0, 0, 99] + [0] * 250 + [1]))
    big_caps = dataclasses.replace(caps, deg_cap=256, v_cap=8192)
    est_h = UpdateShapes.from_estimator(4, 4, heavy, big_caps, m=2)
    _, cand_h, _ = est_h.delta_caps(big_caps, 2)
    _, cand_nh, _ = UpdateShapes(4, 4).delta_caps(big_caps, 2)
    assert cand_h < cand_nh
    # empty graph: estimator degenerates → never-overflow fallback
    empty = GraphStats(n=0, m=0, deg_hist=(0,))
    fb = UpdateShapes.from_estimator(4, 4, empty, caps, m=2)
    assert fb.cand_cap is None and fb.cedge_cap is None


# ---------------------------------------------------------------------------
# Delta-maintained unit-table cache: cold/warm/invalidation + parity
# ---------------------------------------------------------------------------

def test_unit_cache_cold_warm_invalidation_probe():
    """Acceptance: the first batch cold-fills the cache (|units|·m
    listings); every warm batch re-lists exactly |units| tables per
    *invalidated* partition — the §IV-D `fixed` term scales with the
    dirty set, not the graph — and the PROBE counters prove it."""
    g = random_graph(24, 55, seed=111)
    svc = ListingService(g, m=4, backend="host",
                         scheduler=BatchScheduler(max_ops=8))
    svc.register("sq", PATTERN_LIBRARY["q1_square"])
    n_units = len(svc.backend.meta("sq").units)
    m = svc.backend.storage.m

    # --- cold: the cache is empty, every (unit, partition) lists once
    stream_scheduler.reset_probe()
    svc.ingest(sample_update(svc.projected_graph(), 2, 2, seed=112))
    svc.advance()
    cold = svc.metrics[-1]
    assert cold.cache_misses == n_units * m
    assert stream_scheduler.PROBE["cache_misses"] == n_units * m

    # --- warm: only the partitions this delta dirtied re-list
    for b in range(4):
        svc.ingest(sample_update(svc.projected_graph(), 2, 2, seed=120 + b))
        stream_scheduler.reset_probe()
        svc.advance()
        warm = svc.metrics[-1]
        dirty = warm.invalidated_parts
        assert 0 <= dirty <= m
        assert warm.cache_misses == n_units * dirty
        assert warm.cache_hits + warm.cache_misses >= n_units * m
        assert stream_scheduler.PROBE["invalidated_parts"] == dirty
    # warm batches calibrated the scheduler's fixed term downward
    assert svc.scheduler.fixed_miss_rate() < 1.0
    assert svc.scheduler.fixed_cost_warm() < svc.scheduler.fixed_cost_cold() \
        or svc.scheduler.fixed_cost_cold() == 0.0
    # and the cached path stayed exact
    _assert_byte_match(svc, [("sq", PATTERN_LIBRARY["q1_square"])])


def _check_cached_patch_parity(seed0, rounds):
    """nav_join_patch through a delta-maintained PartitionUnitCache ==
    the direct-listing path, byte-for-byte, at every watermark."""
    from repro.core import PartitionUnitCache, build_np_storage
    from repro.core.ddsl import choose_cover
    from repro.core.estimator import GraphStats
    from repro.core.join_tree import minimum_unit_decomposition
    from repro.core.navjoin import NavReport, nav_join_patch
    from repro.core.pattern import symmetry_break
    from repro.core.storage import update_np_storage

    g = random_graph(20, 45, seed=7)
    pat = PATTERN_LIBRARY["q1_square"]
    ord_ = symmetry_break(pat)
    cover = choose_cover(pat, ord_, GraphStats.of(g))
    units = minimum_unit_decomposition(pat, cover)
    storage = build_np_storage(g, 3)
    cache = PartitionUnitCache(storage)
    want_misses = 0
    for b in range(rounds):
        upd = sample_update(storage.graph, 2, 2, seed=seed0 + b)
        storage2, rep = update_np_storage(storage, upd)
        cache.advance(storage2, rep.dirty_parts)
        # cold fill on batch 0, then exactly the dirty partitions
        want_misses += len(units) * (3 if b == 0 else len(rep.dirty_parts))
        r_c, r_p = NavReport(), NavReport()
        cached = nav_join_patch(
            storage2, units, pat, cover, ord_, upd.add, report=r_c,
            provider=cache, seed_fn=cache.seed_fn(cover, ord_, upd.add_codes()))
        plain = nav_join_patch(storage2, units, pat, cover, ord_, upd.add,
                               report=r_p)
        assert _rows(cached.decompress(ord_)[1]) == _rows(plain.decompress(ord_)[1])
        # same tables flowed through the joins — cost metering intact
        assert r_c.local_unit_ints == r_p.local_unit_ints
        assert r_c.patch_matches == r_p.patch_matches
        # warm re-listing is bounded by the dirty partitions
        assert cache.entries() <= len(units) * 3
        storage = storage2
    # exactly cold fill + |units| listings per invalidated partition —
    # never |units|·m per batch
    assert cache.stats.misses == want_misses
    return cache


@pytest.mark.parametrize("seed0", [300, 4711])
def test_cached_patch_byte_parity_50_batches(seed0):
    _check_cached_patch_parity(seed0, rounds=50)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(0, 10_000), st.integers(1, 6))
    def test_cached_patch_byte_parity_fuzz(seed0, rounds):
        _check_cached_patch_parity(seed0, rounds)


def test_provider_bound_to_stale_storage_is_refused():
    """A provider that wasn't advanced to the Φ(d') being patched must
    fail loudly — silently serving stale tables would corrupt patches."""
    from repro.core import PartitionUnitCache, build_np_storage
    from repro.core.ddsl import choose_cover
    from repro.core.estimator import GraphStats
    from repro.core.join_tree import minimum_unit_decomposition
    from repro.core.navjoin import nav_join_patch
    from repro.core.pattern import symmetry_break
    from repro.core.storage import update_np_storage

    g = random_graph(16, 30, seed=9)
    pat = PATTERN_LIBRARY["q2_triangle"]
    ord_ = symmetry_break(pat)
    cover = choose_cover(pat, ord_, GraphStats.of(g))
    units = minimum_unit_decomposition(pat, cover)
    storage = build_np_storage(g, 2)
    cache = PartitionUnitCache(storage)      # bound to Φ(d), not Φ(d')
    upd = sample_update(g, 2, 2, seed=10)
    storage2, _ = update_np_storage(storage, upd)
    with pytest.raises(ValueError, match="different Φ"):
        nav_join_patch(storage2, units, pat, cover, ord_, upd.add,
                       provider=cache)


# ---------------------------------------------------------------------------
# Service snapshot/restore at a watermark
# ---------------------------------------------------------------------------

def test_service_snapshot_restore_host_roundtrip(tmp_path):
    """Snapshot mid-stream (with ops pending beyond the watermark),
    restore, and the restored service is indistinguishable: same
    counts, same committed watermark, the pending ops fold in on the
    next advance, and an identical continuation stays byte-matched."""
    g = random_graph(20, 40, seed=91)
    svc = ListingService(g, m=3, backend="host",
                         scheduler=BatchScheduler(max_ops=5))
    specs = [("tri", PATTERN_LIBRARY["q2_triangle"]),
             ("sq", PATTERN_LIBRARY["q1_square"])]
    for name, pat in specs:
        svc.register(name, pat)
    _stream(svc, rounds=3, d=2, a=2, seed0=93)
    svc.advance()
    svc.ingest(sample_update(svc.projected_graph(), 2, 2, seed=97))  # pending
    snap = str(tmp_path / "snap")
    svc.snapshot(snap)

    svc2 = ListingService.restore(snap, backend="host", m=3,
                                  scheduler=BatchScheduler(max_ops=5))
    assert svc2.committed_watermark == svc.committed_watermark
    assert svc2.counts() == svc.counts()
    assert svc2.journal.tail == svc.journal.tail
    # identical continuation: drain the pending ops, then keep streaming
    svc.advance()
    svc2.advance()
    assert svc2.counts() == svc.counts()
    upd = sample_update(svc.projected_graph(), 2, 2, seed=98)
    svc.ingest(upd)
    svc2.ingest(upd)
    svc.advance()
    svc2.advance()
    assert svc2.counts() == svc.counts()
    _assert_byte_match(svc2, specs)
    assert all(svc2.audit().values())


def test_service_snapshot_restore_is_backend_neutral(tmp_path):
    """A host snapshot restores into a sharded backend: the MatchStore
    is rebuilt from the snapshot table via stack_matches (no
    from-scratch listing), the stream resumes, and stays exact."""
    g = random_graph(18, 35, seed=101)
    svc = ListingService(g, m=2, backend="host",
                         scheduler=BatchScheduler(max_ops=4))
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    _stream(svc, rounds=2, d=2, a=2, seed0=103)
    svc.advance()
    snap = str(tmp_path / "snap")
    svc.snapshot(snap)

    svc2 = ListingService.restore(snap, backend="sharded",
                                  scheduler=BatchScheduler(max_ops=4),
                                  max_add=4, max_del=4)
    assert svc2.counts() == svc.counts()
    upd = sample_update(svc2.projected_graph(), 2, 2, seed=105)
    svc2.ingest(upd)
    svc2.advance()
    assert all(svc2.audit().values())
    fresh = DDSL(svc2.graph, PATTERN_LIBRARY["q2_triangle"], m=4)
    fresh.initial()
    assert _rows(fresh.matches_plain()) == _rows(svc2.backend.matches_plain("tri"))


def test_service_snapshot_reuses_directory_safely(tmp_path):
    """Re-snapshotting into the same directory must commit the *new*
    watermark — and because the old meta.json is deleted before any
    artifact is rewritten, a crash mid-rewrite can never leave a stale
    commit record over newer tables (the restore-accepts-half-snapshot
    hazard)."""
    import os

    g = random_graph(16, 30, seed=121)
    svc = ListingService(g, m=2, backend="host",
                         scheduler=BatchScheduler(max_ops=4))
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    snap = str(tmp_path / "snap")
    _stream(svc, rounds=1, d=2, a=2, seed0=123)
    svc.advance()
    svc.snapshot(snap)
    w1 = svc.committed_watermark
    _stream(svc, rounds=1, d=2, a=2, seed0=124)
    svc.advance()
    svc.snapshot(snap)                      # reuse the directory
    assert svc.committed_watermark > w1
    svc2 = ListingService.restore(snap, backend="host", m=2)
    assert svc2.committed_watermark == svc.committed_watermark
    assert svc2.counts() == svc.counts()
    # crash simulation: artifacts rewritten but meta.json gone (it is
    # deleted first) — restore refuses instead of replaying stale state
    os.remove(os.path.join(snap, "meta.json"))
    with pytest.raises(FileNotFoundError):
        ListingService.restore(snap, backend="host", m=2)


def test_service_restore_rejects_bad_snapshot(tmp_path):
    (tmp_path / "meta.json").write_text('{"kind": "something-else"}\n')
    with pytest.raises(ValueError):
        ListingService.restore(str(tmp_path))
    (tmp_path / "meta.json").write_text(
        '{"kind": "repro.stream.snapshot", "version": 9}\n')
    with pytest.raises(ValueError, match="version"):
        ListingService.restore(str(tmp_path))


def test_journal_compaction_through_service():
    g = random_graph(16, 30, seed=23)
    svc = ListingService(g, m=2, backend="host")
    svc.register("tri", PATTERN_LIBRARY["q2_triangle"])
    _stream(svc, rounds=2, d=2, a=2, seed0=51)
    svc.advance()
    assert svc.compact() == 8
    assert len(svc.journal) == 0 and svc.journal.base == svc.committed_watermark
    # service keeps running after compaction
    _stream(svc, rounds=1, d=2, a=2, seed0=61)
    svc.advance()
    assert svc.audit()["tri"]
