"""Static-shape JAX engine vs host engine (single device, exact equality)."""

import dataclasses
import numpy as np
import pytest

import jax.numpy as jnp

from conftest import random_graph

from repro.core import build_np_storage, symmetry_break
from repro.core.cost import CostModel
from repro.core.ddsl import choose_cover
from repro.core.estimator import GraphStats
from repro.core.join_tree import minimum_unit_decomposition
from repro.core.listing import list_unit_all_parts, list_unit_compressed
from repro.core.pattern import PATTERN_LIBRARY
from repro.core.vcbc import cc_join
from repro.dist import jax_engine as je

CAPS = je.EngineCaps(v_cap=64, deg_cap=32, e_cap=512, match_cap=2048,
                     group_cap=1024, set_cap=32, pair_cap=128)


def _setup(pname, seed=3):
    g = random_graph(40, 100, seed=seed)
    pat = PATTERN_LIBRARY[pname]
    ord_ = symmetry_break(pat)
    cover = choose_cover(pat, ord_, GraphStats.of(g))
    units = minimum_unit_decomposition(pat, cover)
    storage = build_np_storage(g, 4)
    return g, pat, ord_, cover, units, storage


@pytest.mark.parametrize("pname", ["q2_triangle", "q1_square", "q5_house", "q3_diamond"])
def test_unit_listing_matches_host(pname):
    g, pat, ord_, cover, units, storage = _setup(pname)
    for u in units:
        plan = je.build_unit_plan(u.pattern, u.anchor_in(cover), ord_)
        for part in storage.parts:
            host_t = list_unit_compressed(part, u, cover, ord_)
            pt = je.pad_partition(part, CAPS)
            tbl, valid, ovf = je.unit_list(pt, plan, CAPS)
            assert int(ovf) == 0
            tc, skel_cols, ovf2 = je.compress_plain(tbl, valid, plan.cols, cover, CAPS)
            assert int(ovf2) == 0
            back = je.comp_to_host(tc, u.pattern, cover, skel_cols)
            _, ht = host_t.decompress(ord_)
            _, jt = back.decompress(ord_)
            assert set(map(tuple, ht.tolist())) == set(map(tuple, jt.tolist()))


@pytest.mark.parametrize("pname", ["q1_square", "q5_house"])
def test_ccjoin_matches_host(pname):
    g, pat, ord_, cover, units, storage = _setup(pname)
    assert len(units) >= 2
    u1, u2 = units[0], units[1]
    hA = list_unit_all_parts(storage, u1, cover, ord_)
    hB = list_unit_all_parts(storage, u2, cover, ord_)
    hj = cc_join(hA, hB, ord_)
    _, hjt = hj.decompress(ord_)

    def to_tensors(ht):
        colsh, t = ht.decompress(ord_)
        tbl = np.full((CAPS.match_cap, len(colsh)), je.PAD, np.int32)
        tbl[: t.shape[0]] = t
        valid = np.zeros(CAPS.match_cap, bool)
        valid[: t.shape[0]] = True
        return je.compress_plain(jnp.array(tbl), jnp.array(valid), tuple(colsh), cover, CAPS)

    tA, _, _ = to_tensors(hA)
    tB, _, _ = to_tensors(hB)
    jplan = je.JoinPlan.make(u1.pattern, u2.pattern, cover, ord_)
    tJ, ovf = je.ccjoin_local(tA, tB, jplan, CAPS)
    assert int(ovf) == 0
    back = je.comp_to_host(tJ, u1.pattern.union(u2.pattern), cover, jplan.skel_out)
    _, jjt = back.decompress(ord_)
    assert set(map(tuple, hjt.tolist())) == set(map(tuple, jjt.tolist()))


@pytest.mark.parametrize("pname", ["q2_triangle", "q5_house"])
def test_pallas_probes_match_host(pname):
    """use_pallas routes set-intersection + edge probes through the
    Pallas kernels (interpret mode on CPU); results stay byte-identical
    to both the host engine and the non-Pallas device engine."""
    import dataclasses

    g, pat, ord_, cover, units, storage = _setup(pname, seed=5)
    pcaps = dataclasses.replace(CAPS, use_pallas=True)
    caps0 = dataclasses.replace(CAPS, use_pallas=False)

    # unit listing (edge-membership probes in unit_list)
    u = max(units, key=lambda x: x.pattern.m)   # most edge checks
    plan = je.build_unit_plan(u.pattern, u.anchor_in(cover), ord_)
    part = storage.parts[0]
    host_t = list_unit_compressed(part, u, cover, ord_)
    outs = {}
    for caps in (caps0, pcaps):
        pt = je.pad_partition(part, caps)
        tbl, valid, ovf = je.unit_list(pt, plan, caps)
        assert int(ovf) == 0
        tc, skel_cols, _ = je.compress_plain(tbl, valid, plan.cols, cover, caps)
        back = je.comp_to_host(tc, u.pattern, cover, skel_cols)
        outs[caps.use_pallas] = set(map(tuple, back.decompress(ord_)[1].tolist()))
    host_rows = set(map(tuple, host_t.decompress(ord_)[1].tolist()))
    assert outs[False] == outs[True] == host_rows

    # CC-join (compressed-set intersection in ccjoin_local)
    if len(units) >= 2:
        u1, u2 = units[0], units[1]
        hA = list_unit_all_parts(storage, u1, cover, ord_)
        hB = list_unit_all_parts(storage, u2, cover, ord_)
        hj = cc_join(hA, hB, ord_)
        host_rows = set(map(tuple, hj.decompress(ord_)[1].tolist()))
        jplan = je.JoinPlan.make(u1.pattern, u2.pattern, cover, ord_)
        for caps in (caps0, pcaps):
            def to_tensors(ht):
                colsh, t = ht.decompress(ord_)
                tbl = np.full((caps.match_cap, len(colsh)), je.PAD, np.int32)
                tbl[: t.shape[0]] = t
                valid = np.zeros(caps.match_cap, bool)
                valid[: t.shape[0]] = True
                tc, skel_cols, o = je.compress_plain(jnp.array(tbl), jnp.array(valid),
                                                     tuple(colsh), cover, caps)
                assert int(o) == 0
                return tc
            tA = to_tensors(hA)
            tB = to_tensors(hB)
            tJ, ovf = je.ccjoin_local(tA, tB, jplan, caps)
            assert int(ovf) == 0
            back = je.comp_to_host(tJ, u1.pattern.union(u2.pattern), cover,
                                   jplan.skel_out)
            assert set(map(tuple, back.decompress(ord_)[1].tolist())) == host_rows


def test_overflow_is_counted_not_silent():
    g, pat, ord_, cover, units, storage = _setup("q2_triangle")
    tiny = je.EngineCaps(v_cap=64, deg_cap=32, e_cap=512, match_cap=4,
                         group_cap=4, set_cap=4, pair_cap=2)
    plan = je.build_unit_plan(units[0].pattern, units[0].anchor_in(cover), ord_)
    total_host = 0
    total_jax = 0
    total_ovf = 0
    for part in storage.parts:
        host_t = list_unit_compressed(part, units[0], cover, ord_)
        total_host += host_t.count_matches(ord_)
        pt = je.pad_partition(part, tiny)
        tbl, valid, ovf = je.unit_list(pt, plan, tiny)
        total_jax += int(np.asarray(valid).sum())
        total_ovf += int(ovf)
    if total_host > total_jax:
        assert total_ovf > 0  # dropped rows must be accounted


def _padded_extend(pt, tbl, valid, pivot, min_degree, edge_cols, ord_checks,
                   cap, use_pallas):
    """Reference extension: every row × every deg_cap slot at once."""
    k = tbl.shape[1]
    cand = pt.adj[je._row_of(pt, tbl[:, pivot])]
    ok = valid[:, None] & (cand >= 0)
    ok &= pt.deg[je._row_of(pt, cand)] >= min_degree
    for j in range(k):
        ok &= cand != tbl[:, j][:, None]
    for j in edge_cols:
        ok &= je._has_edge(pt, cand, jnp.broadcast_to(tbl[:, j][:, None], cand.shape))
    for j, greater in ord_checks:
        ok &= (cand > tbl[:, j][:, None]) if greater else (cand < tbl[:, j][:, None])
    wide = jnp.concatenate([jnp.repeat(tbl[:, None, :], cand.shape[1], axis=1),
                            cand[:, :, None]], axis=2).reshape(-1, k + 1)
    return je._compact_rows(wide, ok.reshape(-1), cap)


@pytest.mark.parametrize("pname,wcoj", [("q2_triangle", False), ("q3_diamond", False),
                                        ("q4_clique4", True), ("q3_diamond", True)])
def test_chunked_extension_is_bit_identical(monkeypatch, pname, wcoj):
    """Extension over the pivots' real neighbors, block by block, packs
    the same table, validity and overflow as expanding every deg_cap
    slot at once — for blocks that split rows, and under a cap that
    overflows."""
    g, pat, ord_, cover, units, storage = _setup(pname)
    pt = je.pad_partition(storage.parts[0], CAPS)
    if wcoj:
        plan = je.build_wcoj_plan(pat, None, ord_)
        runs = [lambda c, lc=lc: je.wcoj_list(pt, plan, c, lc)
                for lc in ((64, 256, 1024, 2048)[:len(plan.order)],
                           (64, 256, 16, 8)[:len(plan.order)])]
    else:
        u = units[0]
        plan = je.build_unit_plan(u.pattern, u.anchor_in(cover), ord_)
        runs = [lambda c: je.unit_list(pt, plan, c)]
        runs.append(lambda c: je.unit_list(
            pt, plan, dataclasses.replace(c, match_cap=16)))
    for run in runs:
        monkeypatch.setattr(je, "_extend", _padded_extend)
        want = run(CAPS)
        monkeypatch.undo()
        for block in (7, 3 * CAPS.deg_cap, 1 << 20):
            monkeypatch.setattr(je, "_EXPAND_CHUNK", block)
            got = run(CAPS)
            monkeypatch.undo()
            for a, b in zip(want, got):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape,axis", [((1,), 0), ((7,), 0), ((1000,), 0),
                                        ((5, 129), 1), ((33, 4), 0)])
def test_prefix_sum_matches_cumsum(shape, axis):
    x = np.random.default_rng(len(shape) + axis).integers(-3, 9, shape).astype(np.int32)
    got = je._prefix_sum(jnp.asarray(x), axis)
    np.testing.assert_array_equal(np.asarray(got), np.cumsum(x, axis=axis))


@pytest.mark.parametrize("cap", [4, 64])
def test_dedup_rows_one_column_matches_grouping(cap):
    """The keys-only sort path for vertex ids packs the same table,
    validity and overflow as grouping by a permutation sort."""
    rng = np.random.default_rng(cap)
    ids = jnp.asarray(rng.integers(0, 40, (50, 1)).astype(np.int32))
    ok = jnp.asarray(rng.random(50) < 0.7)
    want = je.group_rows(ids, ok, cap)
    got = je.dedup_rows(ids, ok, cap)
    for a, b in zip((want[0], want[1], want[4]), got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape", [(37,), (9, 130), (4, 256)])
def test_probe_pallas_any_query_shape(shape):
    """Lane-padded flattening of multi-dim queries gives the same hits
    as the binary search."""
    rng = np.random.default_rng(sum(shape))
    t = np.unique(np.sort(rng.integers(0, 30, (60, 2)), axis=1), axis=0).astype(np.int32)
    t_hi = np.full(64, -1, np.int32)
    t_lo = np.full(64, -1, np.int32)
    t_hi[:t.shape[0]], t_lo[:t.shape[0]] = t[:, 0], t[:, 1]
    q = np.sort(rng.integers(-1, 30, shape + (2,)), axis=-1).astype(np.int32)
    args = [jnp.asarray(x) for x in (q[..., 0], q[..., 1], t_hi, t_lo)]
    got = je._probe_pallas(*args)
    assert got.shape == shape
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(je.edge_probe(*args, use_pallas=False)))


def _set_rows(rng, n, width, full=False):
    """``[n, width]`` canonical set rows: ascending distinct values, then a
    PAD tail of random length (none if ``full``)."""
    out = np.full((n, width), je.PAD, np.int32)
    for i in range(n):
        k = width if full else int(rng.integers(0, width + 1))
        out[i, :k] = np.sort(rng.choice(4 * width + 8, size=k, replace=False))
    return out


def _filter_set_rows_ref(vals, ok, set_cap):
    out = np.full((vals.shape[0], set_cap), je.PAD, np.int32)
    counts = np.zeros(vals.shape[0], np.int32)
    for i in range(vals.shape[0]):
        kept = vals[i][ok[i]]
        counts[i] = kept.shape[0]
        out[i, :min(set_cap, kept.shape[0])] = kept[:set_cap]
    return out, counts


@pytest.mark.parametrize("case", ["narrow", "equal", "wide", "no_drops", "all_drops",
                                  "full_rows", "all_kept_wide", "merge_dups"])
def test_filter_set_rows_matches_numpy(case):
    """The row repack keeps each row's first ``set_cap`` ``ok`` values,
    PAD-tailed, and counts all of them — for rows narrower than, as wide
    as and wider than ``set_cap``, with PAD tails or none, nothing or
    everything dropped, and the sorted union with duplicates that
    :func:`merge_tables_dev` repacks."""
    rng = np.random.default_rng(sum(map(ord, case)))
    set_cap, n = 8, 64
    width = {"narrow": 5, "equal": 8, "wide": 13}.get(case, 11)
    if case == "merge_dups":
        # Two canonical rows per group, each as wide as set_cap; half the
        # groups hold the same row on both sides, so sorted together a
        # value on both sides sits twice.
        a = _set_rows(rng, n, set_cap)
        b = _set_rows(rng, n, set_cap)
        shared = rng.random(n) < 0.5
        b[shared] = a[shared]
        cat = np.concatenate([a, b], axis=1)
        vals = np.sort(np.where(cat < 0, je._BIG, cat), axis=1).astype(np.int32)
        prev = np.concatenate([np.full((n, 1), -2, np.int32), vals[:, :-1]], axis=1)
        ok = (vals != prev) & (vals != je._BIG) & (rng.random(n) < 0.9)[:, None]
        assert (ok.sum(axis=1) < (vals != je._BIG).sum(axis=1)).any()
    else:
        vals = _set_rows(rng, n, width, full=case in ("full_rows", "all_kept_wide"))
        ok = vals >= 0
        if case == "all_drops":
            ok[:] = False
        elif case not in ("no_drops", "all_kept_wide"):
            ok &= rng.random(vals.shape) < 0.6
            ok[0] = vals[0] >= 0                       # one row keeps all
            ok[1] = False                              # one row keeps none
    want, want_counts = _filter_set_rows_ref(vals, ok, set_cap)
    got, counts = je._filter_set_rows(jnp.asarray(vals), jnp.asarray(ok), set_cap)
    assert got.dtype == jnp.int32 and got.shape == (n, set_cap)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
