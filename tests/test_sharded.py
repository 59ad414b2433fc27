"""Sharded tree programs on a single-device mesh (no fake-device flags).

The full 8-device parity checks live in ``tests/spmd/`` (slow,
subprocess-isolated). These tests exercise the same
``stack_partitions → make_list_step / make_update_step`` path in-process
on whatever devices exist, so the sharded layer gets coverage on every
plain ``pytest`` run.
"""

import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from conftest import random_graph

from repro.core import DDSL, build_np_storage, symmetry_break
from repro.core.cost import CostModel
from repro.core.ddsl import choose_cover
from repro.core.estimator import GraphStats
from repro.core.graph import GraphUpdate
from repro.core.join_tree import minimum_unit_decomposition, optimal_join_tree
from repro.core.navjoin import nav_join_patch
from repro.core.pattern import PATTERN_LIBRARY, Pattern
from repro.core.storage import update_np_storage
from repro.dist import jax_engine as je
from repro.dist import sharded

CAPS = je.EngineCaps(v_cap=64, deg_cap=32, e_cap=512, match_cap=2048,
                     group_cap=2048, set_cap=32, pair_cap=64)


def _mesh_and_m():
    m = jax.local_device_count()
    mesh = jax.make_mesh((m,), ("data",))
    return mesh, m


def _setup(pname, seed=7):
    g = random_graph(36, 90, seed=seed)
    pat = PATTERN_LIBRARY[pname]
    ord_ = symmetry_break(pat)
    stats = GraphStats.of(g)
    cover = choose_cover(pat, ord_, stats)
    tree = optimal_join_tree(pat, cover, CostModel(cover, ord_, stats))
    prog = sharded.build_tree_program(tree, cover, ord_)
    return g, pat, ord_, cover, tree, prog


def _shard_input(pt, mesh):
    specs = sharded.partition_specs(mesh)
    return jax.device_put(pt, jax.tree.map(lambda s: NamedSharding(mesh, s), specs))


@pytest.mark.parametrize("pname", ["q2_triangle", "q1_square", "q5_house"])
def test_list_step_matches_host(pname):
    mesh, m = _mesh_and_m()
    g, pat, ord_, cover, tree, prog = _setup(pname)
    storage = build_np_storage(g, m)
    pt = _shard_input(sharded.stack_partitions(storage, CAPS), mesh)
    step = sharded.make_list_step(prog, mesh, CAPS)
    out, diag = step(pt)
    assert int(diag["overflow"]) == 0

    root = prog.nodes[prog.root]
    skel = np.asarray(out.skeleton).reshape(-1, out.skeleton.shape[-1])
    valid = np.asarray(out.valid).reshape(-1)
    sets = {k: jnp.array(np.asarray(v).reshape(-1, v.shape[-1]))
            for k, v in out.sets.items()}
    t = je.CompTensors(skeleton=jnp.array(skel), valid=jnp.array(valid), sets=sets)
    back = je.comp_to_host(t, root.pattern, cover, root.skel_cols)
    _, jt = back.decompress(ord_)

    eng = DDSL(g, pat, m=m, cover=cover)
    eng.initial()
    _, ht = eng.state.matches.decompress(ord_)
    assert set(map(tuple, ht.tolist())) == set(map(tuple, jt.tolist()))


def test_input_specs_match_stacked_shapes():
    mesh, m = _mesh_and_m()
    g, *_ = _setup("q2_triangle")
    storage = build_np_storage(g, m)
    pt = sharded.stack_partitions(storage, CAPS)
    specs = sharded.ddsl_input_specs(CAPS, m)
    flat_a = jax.tree.leaves(pt)
    flat_s = jax.tree.leaves(specs)
    for a, s in zip(flat_a, flat_s):
        assert tuple(a.shape) == tuple(s.shape)
        assert a.dtype == s.dtype


# ---------------------------------------------------------------------------
# _purge_nonparticipating: exactness for 3 compressed vertices
# ---------------------------------------------------------------------------

def _purge_oracle(sets, ord_pairs):
    """Brute force: value survives iff it appears in some full assignment
    satisfying injectivity + ord over all compressed vertices."""
    labels = sorted(sets)
    ord_set = set(ord_pairs)
    keep = {u: set() for u in labels}
    for combo in itertools.product(*[sets[u] for u in labels]):
        asg = dict(zip(labels, combo))
        if len(set(combo)) != len(combo):
            continue
        ok = True
        for u, w in itertools.permutations(labels, 2):
            if (u, w) in ord_set and not asg[u] < asg[w]:
                ok = False
        if ok:
            for u in labels:
                keep[u].add(asg[u])
    return keep


def _run_purge(sets, ord_pairs, set_cap=8):
    labels = sorted(sets)
    g_sets = {}
    for u in labels:
        arr = np.full((1, set_cap), je.PAD, np.int32)
        vals = sorted(sets[u])
        arr[0, :len(vals)] = vals
        g_sets[u] = jnp.asarray(arr)
    tc = je.CompTensors(skeleton=jnp.zeros((1, 1), jnp.int32),
                        valid=jnp.ones((1,), bool), sets=g_sets)
    out = sharded._purge_nonparticipating(tc, tuple(labels), tuple(ord_pairs), set_cap)
    got = {u: set(int(x) for x in np.asarray(out.sets[u])[0] if x >= 0) for u in labels}
    return got, bool(np.asarray(out.valid)[0])


def test_purge_three_compressed_vertices_exact_on_crafted_case():
    # Pairwise screening keeps 3 ∈ S₁ (partners exist in S₂ and S₃
    # separately) but no full triple satisfies 1≺2≺3 — the ≤2-exact
    # purge of PR 1 would leave the value (and the group) alive.
    sets = {1: {3}, 2: {5}, 3: {5}}
    ord_pairs = [(1, 2), (2, 3)]
    got, valid = _run_purge(sets, ord_pairs)
    assert not valid and all(not v for v in got.values())


@pytest.mark.parametrize("seed", range(8))
def test_purge_three_compressed_vertices_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    sets = {u: set(rng.choice(10, size=rng.integers(1, 5), replace=False).tolist())
            for u in (1, 2, 3)}
    all_ords = [(1, 2), (1, 3), (2, 3)]
    ord_pairs = [p for p in all_ords if rng.random() < 0.5]
    want = _purge_oracle(sets, ord_pairs)
    got, valid = _run_purge(sets, ord_pairs)
    assert got == {u: set(v) for u, v in want.items()}
    assert valid == any(want[u] for u in want)


def test_purge_two_compressed_vertices_matches_oracle():
    for seed in range(6):
        rng = np.random.default_rng(100 + seed)
        sets = {1: set(rng.choice(8, size=rng.integers(1, 4), replace=False).tolist()),
                2: set(rng.choice(8, size=rng.integers(1, 4), replace=False).tolist())}
        ord_pairs = [(1, 2)] if seed % 2 else []
        want = _purge_oracle(sets, ord_pairs)
        got, valid = _run_purge(sets, ord_pairs)
        assert got == {u: set(v) for u, v in want.items()}


# A cover leaving THREE compressed vertices: V_c = {0, 1}, comp = {2, 3, 4},
# decomposed into two overlapping R1 units — chains share skeletons, so the
# patch path exercises the generalized purge end to end.
PAT_3COMP = Pattern.make([(0, 1), (0, 2), (0, 3), (1, 3), (1, 4)])


def test_update_step_matches_host_three_compressed_vertices():
    mesh, m = _mesh_and_m()
    g = random_graph(30, 75, seed=11)
    pat = PAT_3COMP
    ord_ = symmetry_break(pat)
    cover = (0, 1)
    stats = GraphStats.of(g)
    tree = optimal_join_tree(pat, cover, CostModel(cover, ord_, stats))
    prog = sharded.build_tree_program(tree, cover, ord_)
    units = minimum_unit_decomposition(pat, cover)
    assert len(set(pat.vertices) - set(cover)) == 3 and len(units) >= 2
    storage = build_np_storage(g, m)

    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        ecur = storage.graph.edges()
        dele = ecur[rng.choice(ecur.shape[0], size=4, replace=False)]
        existing = set(map(tuple, ecur.tolist()))
        add = set()
        while len(add) < 4:
            a, b = int(rng.integers(30)), int(rng.integers(30))
            if a != b and (min(a, b), max(a, b)) not in existing:
                add.add((min(a, b), max(a, b)))
        add = np.array(sorted(add))
        upd = GraphUpdate(delete=dele, add=add)

        storage2, _ = update_np_storage(storage, upd)
        patch_host = nav_join_patch(storage2, units, pat, cover, ord_, add)
        _, pht = patch_host.decompress(ord_)

        pt = _shard_input(sharded.stack_partitions(storage, CAPS), mesh)
        step = sharded.make_update_step(prog, units, mesh, CAPS,
                                        sharded.UpdateShapes(n_add=4, n_del=4))
        _, patch, diag = step(pt, jnp.asarray(add, jnp.int32),
                              jnp.asarray(dele, jnp.int32))
        assert int(diag["overflow"]) == 0
        skel = np.asarray(patch.skeleton).reshape(-1, patch.skeleton.shape[-1])
        valid = np.asarray(patch.valid).reshape(-1)
        sets = {k: jnp.array(np.asarray(v).reshape(-1, v.shape[-1]))
                for k, v in patch.sets.items()}
        t = je.CompTensors(skeleton=jnp.array(skel), valid=jnp.array(valid), sets=sets)
        back = je.comp_to_host(t, pat, cover, (0, 1))
        _, jt = back.decompress(ord_)
        assert set(map(tuple, pht.tolist())) == set(map(tuple, jt.tolist()))
        storage = storage2   # stream the next update over the new state


def test_split_steps_compose_to_fused_update_step():
    """make_storage_update_step + make_patch_step == make_update_step."""
    mesh, m = _mesh_and_m()
    g, pat, ord_, cover, tree, prog = _setup("q2_triangle")
    units = minimum_unit_decomposition(pat, cover)
    storage = build_np_storage(g, m)
    rng = np.random.default_rng(5)
    ecur = g.edges()
    dele = ecur[rng.choice(ecur.shape[0], size=2, replace=False)]
    existing = set(map(tuple, ecur.tolist()))
    add = set()
    while len(add) < 2:
        a, b = int(rng.integers(36)), int(rng.integers(36))
        if a != b and (min(a, b), max(a, b)) not in existing:
            add.add((min(a, b), max(a, b)))
    add = np.array(sorted(add))

    ush = sharded.UpdateShapes(n_add=2, n_del=2)
    pt = _shard_input(sharded.stack_partitions(storage, CAPS), mesh)
    addj = jnp.asarray(add, jnp.int32)
    delj = jnp.asarray(dele, jnp.int32)

    fused = sharded.make_update_step(prog, units, mesh, CAPS, ush)
    pt2_f, patch_f, diag_f = fused(pt, addj, delj)

    sstep = sharded.make_storage_update_step(mesh, CAPS, ush)
    pstep = sharded.make_patch_step(prog, units, mesh, CAPS)
    pt2_s, sdiag = sstep(pt, addj, delj)
    patch_s, pdiag = pstep(pt2_s, addj)

    for a_, b_ in zip(jax.tree.leaves(pt2_f), jax.tree.leaves(pt2_s)):
        assert (np.asarray(a_) == np.asarray(b_)).all()
    for a_, b_ in zip(jax.tree.leaves(patch_f), jax.tree.leaves(patch_s)):
        assert (np.asarray(a_) == np.asarray(b_)).all()
    assert int(diag_f["overflow"]) == int(sdiag["overflow"]) + int(pdiag["overflow"])
    assert int(diag_f["patch_groups"]) == int(pdiag["patch_groups"])


# ---------------------------------------------------------------------------
# Candidate-restricted storage update (Alg. 4 C1–C3 on device)
# ---------------------------------------------------------------------------

def _sample_batch(graph, rng, n_ops, n):
    """One well-formed update batch: delete existing, add absent edges."""
    ecur = graph.edges()
    dele = ecur[rng.choice(ecur.shape[0], size=n_ops, replace=False)]
    existing = set(map(tuple, ecur.tolist()))
    add = set()
    while len(add) < n_ops:
        a, b = int(rng.integers(n)), int(rng.integers(n))
        if a != b and (min(a, b), max(a, b)) not in existing:
            add.add((min(a, b), max(a, b)))
    return np.array(sorted(add)), dele


@pytest.mark.parametrize("use_pallas", [False, True])
def test_delta_storage_step_byte_matches_full_oracle_50_batches(use_pallas):
    """Acceptance: the candidate-restricted update and the full-gather
    oracle produce byte-identical partitions over a randomized 50-batch
    update stream, under both Pallas settings."""
    from repro.core.storage import update_np_storage

    import dataclasses as _dc

    mesh, m = _mesh_and_m()
    n = 30
    g = random_graph(n, 70, seed=21)
    caps = _dc.replace(CAPS, use_pallas=use_pallas)
    storage = build_np_storage(g, m)
    pt = _shard_input(sharded.stack_partitions(storage, caps), mesh)
    ush = sharded.UpdateShapes(n_add=3, n_del=3)
    full = sharded.make_storage_update_step(mesh, caps, ush, mode="full")
    delta = sharded.make_storage_update_step(mesh, caps, ush, mode="delta")

    rng = np.random.default_rng(33)
    cur = storage
    batches = 50 if not use_pallas else 12   # interpret-mode kernel is slower
    for b in range(batches):
        add, dele = _sample_batch(cur.graph, rng, 3, n)
        aj = jnp.asarray(add, jnp.int32)
        dj = jnp.asarray(dele, jnp.int32)
        ptf, diag_f = full(pt, aj, dj)
        ptd, diag_d = delta(pt, aj, dj)
        for xf, xd in zip(jax.tree.leaves(ptf), jax.tree.leaves(ptd)):
            assert (np.asarray(xf) == np.asarray(xd)).all()
        assert int(diag_f["overflow"]) == 0 and int(diag_d["overflow"]) == 0
        # per-batch candidate counters: fresh each call, delta-bounded
        c1 = 2 * (add.shape[0] + dele.shape[0])
        assert 0 < int(diag_d["cand_vertices"]) <= c1 * (caps.deg_cap + 1)
        assert 0 < int(diag_d["cand_edges"]) <= c1 * caps.deg_cap
        pt = ptd
        cur, _ = update_np_storage(cur, GraphUpdate(delete=dele, add=add))

    # end state still equals a from-scratch host rebuild
    rebuilt = build_np_storage(cur.graph, m)
    for j in range(m):
        ehi = np.asarray(pt.edge_hi)[j]
        elo = np.asarray(pt.edge_lo)[j]
        got = set((int(a), int(b)) for a, b in zip(ehi, elo) if a >= 0)
        want = set((int(c >> 32), int(c & 0xFFFFFFFF)) for c in rebuilt.parts[j].codes)
        assert got == want


def test_delta_step_edge_cases_match_oracle():
    """Fresh vertex ids, padded batch slots, and out-of-bounds inserts
    all behave identically to the full-gather oracle (including the
    overflow count for the oob insert)."""
    mesh, m = _mesh_and_m()
    g = random_graph(20, 40, seed=2)
    caps = je.EngineCaps(v_cap=64, deg_cap=16, e_cap=256, match_cap=1024,
                         group_cap=1024, set_cap=16, pair_cap=32)
    storage = build_np_storage(g, m)
    pt = _shard_input(sharded.stack_partitions(storage, caps), mesh)
    ush = sharded.UpdateShapes(n_add=2, n_del=2)
    full = sharded.make_storage_update_step(mesh, caps, ush, mode="full")
    delta = sharded.make_storage_update_step(mesh, caps, ush, mode="delta")

    # brand-new vertices 40/55 + a padded delete slot
    add = jnp.asarray([[40, 55], [3, 40]], jnp.int32)
    dele = jnp.asarray(np.concatenate([g.edges()[:1], [[-1, -1]]]), jnp.int32)
    ptf, df = full(pt, add, dele)
    ptd, dd = delta(pt, add, dele)
    for x, y in zip(jax.tree.leaves(ptf), jax.tree.leaves(ptd)):
        assert (np.asarray(x) == np.asarray(y)).all()
    assert int(df["overflow"]) == 0 and int(dd["overflow"]) == 0

    # an out-of-bounds insert is counted, skipped, and corrupts nothing
    addo = jnp.asarray([[0, m * 64 + 5], [-1, -1]], jnp.int32)
    delz = jnp.full((2, 2), -1, jnp.int32)
    ptf2, df2 = full(pt, addo, delz)
    ptd2, dd2 = delta(pt, addo, delz)
    for x, y in zip(jax.tree.leaves(ptf2), jax.tree.leaves(ptd2)):
        assert (np.asarray(x) == np.asarray(y)).all()
    assert int(df2["overflow"]) == int(dd2["overflow"]) == 1


def test_delta_step_tight_candidate_caps_count_overflow():
    """Explicit (too small) candidate caps must surface in diag, never
    silently truncate."""
    mesh, m = _mesh_and_m()
    g = random_graph(30, 75, seed=5)
    storage = build_np_storage(g, m)
    pt = _shard_input(sharded.stack_partitions(storage, CAPS), mesh)
    ush = sharded.UpdateShapes(n_add=3, n_del=3, cand_cap=2, cedge_cap=2)
    step = sharded.make_storage_update_step(mesh, CAPS, ush, mode="delta")
    rng = np.random.default_rng(8)
    add, dele = _sample_batch(g, rng, 3, 30)
    _, diag = step(pt, jnp.asarray(add, jnp.int32), jnp.asarray(dele, jnp.int32))
    assert int(diag["overflow"]) > 0


def test_update_step_mode_full_and_delta_agree_end_to_end():
    """Fused make_update_step: both modes give identical partitions,
    patches, and patch_groups."""
    mesh, m = _mesh_and_m()
    g, pat, ord_, cover, tree, prog = _setup("q1_square")
    units = minimum_unit_decomposition(pat, cover)
    storage = build_np_storage(g, m)
    rng = np.random.default_rng(17)
    add, dele = _sample_batch(g, rng, 3, 36)
    pt = _shard_input(sharded.stack_partitions(storage, CAPS), mesh)
    ush = sharded.UpdateShapes(n_add=3, n_del=3)
    aj, dj = jnp.asarray(add, jnp.int32), jnp.asarray(dele, jnp.int32)
    pt2_f, patch_f, diag_f = sharded.make_update_step(prog, units, mesh, CAPS,
                                                      ush, mode="full")(pt, aj, dj)
    pt2_d, patch_d, diag_d = sharded.make_update_step(prog, units, mesh, CAPS,
                                                      ush, mode="delta")(pt, aj, dj)
    for a_, b_ in zip(jax.tree.leaves(pt2_f), jax.tree.leaves(pt2_d)):
        assert (np.asarray(a_) == np.asarray(b_)).all()
    for a_, b_ in zip(jax.tree.leaves(patch_f), jax.tree.leaves(patch_d)):
        assert (np.asarray(a_) == np.asarray(b_)).all()
    assert int(diag_f["patch_groups"]) == int(diag_d["patch_groups"])


# ---------------------------------------------------------------------------
# Device-resident match maintenance: filter / merge / count primitives
# ---------------------------------------------------------------------------

def _pad_table(table, group_cap, set_cap):
    """Host CompressedTable → padded CompTensors (test-only helper)."""
    G = table.n_groups
    assert G <= group_cap
    S = len(table.skeleton_cols)
    skel = np.full((group_cap, S), je.PAD, np.int32)
    skel[:G] = table.skeleton
    valid = np.zeros(group_cap, bool)
    valid[:G] = True
    sets = {}
    for v, r in table.comp.items():
        arr = np.full((group_cap, set_cap), je.PAD, np.int32)
        for g in range(G):
            vals = r.values[r.offsets[g]: r.offsets[g + 1]]
            assert vals.shape[0] <= set_cap
            arr[g, : vals.shape[0]] = vals
        sets[v] = jnp.asarray(arr)
    return je.CompTensors(skeleton=jnp.asarray(skel), valid=jnp.asarray(valid),
                          sets=sets)


def _table_rows(table, ord_):
    return set(map(tuple, table.decompress(ord_)[1].tolist()))


def _tensor_rows(tc, pattern, cover, skel_cols, ord_):
    back = je.comp_to_host(tc, pattern, cover, skel_cols)
    return _table_rows(back, ord_)


def _maintenance_fixture(pname_or_pat, seed, cover=None):
    from repro.core.incremental import incremental_update  # noqa: F401

    g = random_graph(30, 70, seed=seed)
    pat = (PATTERN_LIBRARY[pname_or_pat] if isinstance(pname_or_pat, str)
           else pname_or_pat)
    ord_ = symmetry_break(pat)
    stats = GraphStats.of(g)
    cover = choose_cover(pat, ord_, stats) if cover is None else cover
    eng = DDSL(g, pat, m=1, cover=cover)
    eng.initial()
    return g, pat, ord_, cover, eng


@pytest.mark.parametrize("use_pallas", [False, True])
def test_filter_deleted_dev_matches_host(use_pallas):
    from repro.core.incremental import filter_deleted

    g, pat, ord_, cover, eng = _maintenance_fixture("q1_square", seed=19)
    table = eng.state.matches
    tc = _pad_table(table, 256, 16)
    skel_pairs, comp_pairs = je.deleted_edge_cols(pat, table.skeleton_cols)
    rng = np.random.default_rng(3)
    dele = g.edges()[rng.choice(g.num_edges, size=5, replace=False)]
    d = np.stack([dele.min(axis=1), dele.max(axis=1)], axis=1)
    d_tbl, _, _ = je.dedup_rows(jnp.asarray(d, jnp.int32), jnp.ones(5, bool), 5)
    out, removed = je.filter_deleted_dev(
        tc, skel_pairs, comp_pairs, d_tbl[:, 0], d_tbl[:, 1], 16,
        use_pallas=use_pallas)
    want = filter_deleted(table, dele)
    assert _tensor_rows(out, pat, cover, table.skeleton_cols, ord_) == \
        _table_rows(want, ord_)
    assert int(removed) == table.n_groups - want.n_groups


def test_merge_tables_dev_matches_host():
    from repro.core.incremental import merge_tables

    g, pat, ord_, cover, eng = _maintenance_fixture("q2_triangle", seed=23)
    # two overlapping halves of the match set (unequal set widths on
    # purpose: store-wide vs patch-narrow)
    table = eng.state.matches
    cols, rows = table.decompress(ord_)
    from repro.core.vcbc import compress_table
    h = rows.shape[0] // 2
    ta = compress_table(pat, cover, cols, rows[: 2 * h])
    tb = compress_table(pat, cover, cols, rows[h:])
    ca = _pad_table(ta, 128, 16)
    cb = _pad_table(tb, 128, 8)
    out, ovf = je.merge_tables_dev(ca, cb, 256, 16)
    want = merge_tables(ta, tb)
    assert int(ovf) == 0
    assert _tensor_rows(out, pat, cover, table.skeleton_cols, ord_) == \
        _table_rows(want, ord_)
    # forced-small caps overflow loudly, never silently
    _, ovf2 = je.merge_tables_dev(ca, cb, max(want.n_groups - 3, 1), 16)
    assert int(ovf2) > 0


@pytest.mark.parametrize("pat,cover", [
    ("q2_triangle", None),          # 1 compressed vertex
    ("q1_square", None),            # 2 compressed vertices
    (PAT_3COMP, (0, 1)),            # 3 compressed vertices (einsum path)
])
def test_count_matches_dev_matches_host(pat, cover):
    g, p, ord_, cover, eng = _maintenance_fixture(pat, seed=29, cover=cover)
    table = eng.state.matches
    tc = _pad_table(table, 512, 32)
    got = int(je.count_matches_dev(tc, table.skeleton_cols, ord_))
    assert got == table.count_matches(ord_) == eng.count()


def test_count_matches_dev_seven_compressed_vertices():
    """k=7 walks the einsum alphabet past 'g' — the group axis label
    must never collide with a vertex label (regression)."""
    rng = np.random.default_rng(7)
    labels = list(range(1, 8))
    sets = {u: sorted(rng.choice(12, size=3, replace=False).tolist())
            for u in labels}
    ord_pairs = [(1, 2), (3, 4)]
    arrs = {}
    for u in labels:
        a = np.full((1, 4), je.PAD, np.int32)
        a[0, :3] = sets[u]
        arrs[u] = jnp.asarray(a)
    tc = je.CompTensors(skeleton=jnp.full((1, 1), 99, jnp.int32),
                        valid=jnp.ones((1,), bool), sets=arrs)
    got = int(je.count_matches_dev(tc, (0,), ord_pairs))
    want = 0
    for combo in itertools.product(*[sets[u] for u in labels]):
        if len(set(combo)) != len(combo) or 99 in combo:
            continue
        asg = dict(zip(labels, combo))
        if all(asg[a] < asg[b] for a, b in ord_pairs):
            want += 1
    assert got == want and want > 0


def _random_count_tensors(G, S, k, seed=0):
    rng = np.random.default_rng(seed)
    sets = {}
    for u in range(1, k + 1):
        a = np.full((G, S), je.PAD, np.int32)
        for g in range(G):
            w = int(rng.integers(2, S + 1))
            a[g, :w] = np.sort(rng.choice(40, size=w, replace=False))
        sets[u] = jnp.asarray(a)
    skel = jnp.asarray(rng.integers(50, 60, size=(G, 1)).astype(np.int32))
    return je.CompTensors(skeleton=skel, valid=jnp.ones((G,), bool), sets=sets)


@pytest.mark.parametrize("k", [4, 5])
def test_count_matches_dev_chunked_matches_bruteforce(k, monkeypatch):
    """k ≥ 4 routes through the lax.map group chunking — counts must be
    exact for any chunk/G alignment (including a ragged last chunk)."""
    monkeypatch.setattr(je, "_COUNT_CHUNK", 4)
    G, S = 10, 4                      # G = 10 ⇒ chunks of 4, 4, 2
    tc = _random_count_tensors(G, S, k, seed=k)
    ord_pairs = ((1, 2), (3, 4))
    got = int(je.count_matches_dev(tc, (0,), ord_pairs))
    want = 0
    skel = np.asarray(tc.skeleton)
    for g in range(G):
        vals = {u: [int(x) for x in np.asarray(tc.sets[u])[g] if x >= 0]
                for u in tc.sets}
        for combo in itertools.product(*[vals[u] for u in sorted(vals)]):
            if len(set(combo)) != len(combo) or int(skel[g, 0]) in combo:
                continue
            asg = dict(zip(sorted(vals), combo))
            if all(asg[a] < asg[b] for a, b in ord_pairs):
                want += 1
    assert got == want and want > 0


def test_count_matches_dev_chunked_memory_bounded():
    """Regression: at k = 5 the contraction intermediate is O(G·S⁴);
    the chunked lax.map keeps compiled temp memory under the full
    G-sized intermediate (it was ~G/chunk × that before chunking)."""
    G, S, k = 256, 8, 5
    tc = _random_count_tensors(G, S, k, seed=3)
    fn = jax.jit(lambda t: je.count_matches_dev(t, (0,), ((1, 2),)))
    ma = fn.lower(tc).compile().memory_analysis()
    if ma is None or not hasattr(ma, "temp_size_in_bytes"):
        pytest.skip("backend exposes no memory analysis")
    full_intermediate = G * S ** (k - 1) * 4
    assert ma.temp_size_in_bytes < full_intermediate, \
        f"temp {ma.temp_size_in_bytes}B >= unchunked intermediate {full_intermediate}B"


@pytest.mark.parametrize("use_pallas", [False, True])
def test_maintain_step_carry_matches_uncached(use_pallas):
    """Cached-vs-uncached parity: the carry-threaded maintain step
    (persistent unit tables, lax.cond refresh on part_dirty) must
    byte-match the carry-free oracle — stores, patches and counts —
    over a streamed batch sequence, under both Pallas settings."""
    import dataclasses as _dc

    mesh, m = _mesh_and_m()
    g, p, ord_, cover, eng = _maintenance_fixture("q1_square", seed=47)
    caps = _dc.replace(CAPS, use_pallas=use_pallas)
    stats = GraphStats.of(g)
    tree = optimal_join_tree(p, cover, CostModel(cover, ord_, stats))
    prog = sharded.build_tree_program(tree, cover, ord_)
    units = minimum_unit_decomposition(p, cover)
    storage = build_np_storage(g, m)
    pt = _shard_input(sharded.stack_partitions(storage, caps), mesh)
    out, _ = sharded.make_list_step(prog, mesh, caps)(pt)
    store_caps = sharded.match_caps(p, cover, ord_, stats, caps)
    st, _ = sharded.make_init_store_step(prog, mesh, caps, store_caps)(out)
    st_c = jax.tree.map(lambda x: x, st)

    ucaps = sharded.unit_table_caps(units, cover, ord_, stats, caps)
    carry, rdiag = sharded.make_unit_refresh_step(prog, units, mesh, caps,
                                                  ucaps)(pt)
    assert int(rdiag["overflow"]) == 0
    ush = sharded.UpdateShapes(n_add=3, n_del=3)
    sstep = sharded.make_storage_update_step(mesh, caps, ush)
    oracle = sharded.make_maintain_step(prog, units, mesh, caps, store_caps)
    cached = sharded.make_maintain_step(prog, units, mesh, caps, store_caps,
                                        unit_caps=ucaps)

    rng = np.random.default_rng(49)
    cur = storage
    batches = 2 if use_pallas else 5
    for b in range(batches):
        add, dele = _sample_batch(cur.graph, rng, 3, 30)
        upd = GraphUpdate(delete=dele, add=add)
        cur, _ = update_np_storage(cur, upd)
        aj, dj = jnp.asarray(add, jnp.int32), jnp.asarray(dele, jnp.int32)
        pt, sdiag = sstep(pt, aj, dj)
        st, patch_o, odiag = oracle(pt, st, aj, dj)
        st_c, patch_c, carry, cdiag = cached(pt, st_c, carry,
                                             sdiag["part_dirty"], aj, dj)
        assert int(odiag["count"]) == int(cdiag["count"])
        assert int(cdiag["unit_refreshes"]) <= m
        for a_, b_ in zip(jax.tree.leaves(patch_o), jax.tree.leaves(patch_c)):
            assert (np.asarray(a_) == np.asarray(b_)).all()
        for a_, b_ in zip(jax.tree.leaves(st), jax.tree.leaves(st_c)):
            assert (np.asarray(a_) == np.asarray(b_)).all()


@pytest.mark.parametrize("use_pallas", [False, True])
def test_maintain_mega_step_matches_per_pattern(use_pallas):
    """Fused multi-pattern megastep ≡ per-pattern maintain steps: one
    dispatch maintaining triangle + square over a randomized batch
    stream must be byte-identical — stores, patches, carries and diag
    scalars — to running each pattern's carry-threaded step alone,
    under both Pallas settings."""
    import dataclasses as _dc

    mesh, m = _mesh_and_m()
    g = random_graph(30, 70, seed=47)
    caps = _dc.replace(CAPS, use_pallas=use_pallas)
    stats = GraphStats.of(g)
    storage = build_np_storage(g, m)
    pt = _shard_input(sharded.stack_partitions(storage, caps), mesh)

    specs, ref_steps, stores, carries = [], {}, {}, {}
    for name in ("q2_triangle", "q1_square"):
        p = PATTERN_LIBRARY[name]
        ord_ = symmetry_break(p)
        cover = choose_cover(p, ord_, stats)
        tree = optimal_join_tree(p, cover, CostModel(cover, ord_, stats))
        prog = sharded.build_tree_program(tree, cover, ord_)
        units = minimum_unit_decomposition(p, cover)
        out, _ = sharded.make_list_step(prog, mesh, caps)(pt)
        store_caps = sharded.match_caps(p, cover, ord_, stats, caps)
        st, idiag = sharded.make_init_store_step(prog, mesh, caps, store_caps)(out)
        assert int(idiag["overflow"]) == 0
        ucaps = sharded.unit_table_caps(units, cover, ord_, stats, caps)
        carry, _ = sharded.make_unit_refresh_step(prog, units, mesh, caps,
                                                  ucaps)(pt)
        specs.append(sharded.MaintainSpec(name=name, prog=prog,
                                          units=tuple(units),
                                          store=store_caps, unit_caps=ucaps))
        ref_steps[name] = sharded.make_maintain_step(
            prog, units, mesh, caps, store_caps, unit_caps=ucaps)
        stores[name] = st
        carries[name] = carry

    mega = sharded.make_maintain_mega_step(specs, mesh, caps)
    sstep = sharded.make_storage_update_step(mesh, caps,
                                             sharded.UpdateShapes(n_add=3, n_del=3))
    ref_stores = {n: jax.tree.map(lambda x: x, s) for n, s in stores.items()}
    ref_carries = {n: jax.tree.map(lambda x: x, c) for n, c in carries.items()}

    rng = np.random.default_rng(53)
    cur = storage
    batches = 2 if use_pallas else 5
    for b in range(batches):
        add, dele = _sample_batch(cur.graph, rng, 3, 30)
        upd = GraphUpdate(delete=dele, add=add)
        cur, _ = update_np_storage(cur, upd)
        aj, dj = jnp.asarray(add, jnp.int32), jnp.asarray(dele, jnp.int32)
        pt, sdiag = sstep(pt, aj, dj)
        dirty = sdiag["part_dirty"]
        stores, patches, carries, mdiag = mega(pt, stores, carries, dirty,
                                               aj, dj)
        for name in ref_steps:
            st_r, patch_r, carry_r, rdiag = ref_steps[name](
                pt, ref_stores[name], ref_carries[name], dirty, aj, dj)
            ref_stores[name], ref_carries[name] = st_r, carry_r
            for got, want in ((stores[name], st_r), (patches[name], patch_r),
                              (carries[name], carry_r)):
                for a_, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                    assert (np.asarray(a_) == np.asarray(b_)).all(), \
                        f"batch {b} {name}: megastep output drift"
            for k in rdiag:
                assert int(mdiag[name][k]) == int(rdiag[k]), \
                    f"batch {b} {name}: diag[{k}] drift"


def test_patch_step_carry_matches_uncached():
    """Same parity for the standalone patch step: (patch, carry', diag)
    from the carry variant == the carry-free patch, with the carry
    refreshed only on dirty devices."""
    mesh, m = _mesh_and_m()
    g, pat, ord_, cover, tree, prog = _setup("q2_triangle")
    units = minimum_unit_decomposition(pat, cover)
    storage = build_np_storage(g, m)
    stats = GraphStats.of(g)
    rng = np.random.default_rng(5)
    add, dele = _sample_batch(g, rng, 2, 36)
    ush = sharded.UpdateShapes(n_add=2, n_del=2)
    pt = _shard_input(sharded.stack_partitions(storage, CAPS), mesh)
    addj = jnp.asarray(add, jnp.int32)
    delj = jnp.asarray(dele, jnp.int32)

    sstep = sharded.make_storage_update_step(mesh, CAPS, ush)
    pt2, sdiag = sstep(pt, addj, delj)
    ucaps = sharded.unit_table_caps(units, cover, ord_, stats, CAPS)
    carry, _ = sharded.make_unit_refresh_step(prog, units, mesh, CAPS,
                                              ucaps)(pt2)
    plain = sharded.make_patch_step(prog, units, mesh, CAPS)
    withc = sharded.make_patch_step(prog, units, mesh, CAPS, unit_caps=ucaps)
    patch_p, pdiag = plain(pt2, addj)
    patch_c, carry2, cdiag = withc(pt2, carry, sdiag["part_dirty"], addj)
    for a_, b_ in zip(jax.tree.leaves(patch_p), jax.tree.leaves(patch_c)):
        assert (np.asarray(a_) == np.asarray(b_)).all()
    assert int(pdiag["patch_groups"]) == int(cdiag["patch_groups"])


def test_match_store_stack_and_flatten_roundtrip():
    from repro.core.incremental import merge_tables  # noqa: F401

    g, pat, ord_, cover, eng = _maintenance_fixture("q1_square", seed=31)
    table = eng.state.matches
    store_caps = sharded.StoreCaps(group_cap=128, set_cap=16)
    st = sharded.stack_matches(table, 4, store_caps)
    assert st.skeleton.shape[0] == 4
    assert _tensor_rows(st.flatten(), pat, cover, table.skeleton_cols, ord_) == \
        _table_rows(table, ord_)
    # shard too small for its owners → loud sizing error
    with pytest.raises(ValueError):
        sharded.stack_matches(table, 1, sharded.StoreCaps(group_cap=2, set_cap=16))


@pytest.mark.parametrize("pat,cover,use_pallas", [
    ("q2_triangle", None, False),
    ("q1_square", None, True),
    (PAT_3COMP, (0, 1), False),
])
def test_maintain_step_matches_host_apply_update(pat, cover, use_pallas):
    """Fused maintain (patch ∘ filter ∘ merge ∘ count) over a streamed
    sequence of batches == host apply_update_to_matches, counts from
    the device reduction, store stays exact across batches."""
    import dataclasses as _dc

    from repro.core.incremental import apply_update_to_matches

    mesh, m = _mesh_and_m()
    g, p, ord_, cover, _ = _maintenance_fixture(pat, seed=37, cover=cover)
    caps = _dc.replace(CAPS, use_pallas=use_pallas)
    stats = GraphStats.of(g)
    tree = optimal_join_tree(p, cover, CostModel(cover, ord_, stats))
    prog = sharded.build_tree_program(tree, cover, ord_)
    units = minimum_unit_decomposition(p, cover)
    storage = build_np_storage(g, m)
    pt = _shard_input(sharded.stack_partitions(storage, caps), mesh)

    list_step = sharded.make_list_step(prog, mesh, caps)
    out, ldiag = list_step(pt)
    assert int(ldiag["overflow"]) == 0
    store_caps = sharded.match_caps(p, cover, ord_, stats, caps)
    init_step = sharded.make_init_store_step(prog, mesh, caps, store_caps)
    st, idiag = init_step(out)
    assert int(idiag["overflow"]) == 0

    host = DDSL(g, p, m=m, cover=cover)
    host.initial()
    assert int(idiag["count"]) == host.count()
    matches = host.state.matches

    ush = sharded.UpdateShapes(n_add=3, n_del=3)
    sstep = sharded.make_storage_update_step(mesh, caps, ush)
    mstep = sharded.make_maintain_step(prog, units, mesh, caps, store_caps)
    rng = np.random.default_rng(41)
    cur = storage
    skel_cols = prog.nodes[prog.root].skel_cols
    batches = 3 if use_pallas else 6       # interpret-mode kernel is slower
    for b in range(batches):
        add, dele = _sample_batch(cur.graph, rng, 3, 30)
        aj, dj = jnp.asarray(add, jnp.int32), jnp.asarray(dele, jnp.int32)
        upd = GraphUpdate(delete=dele, add=add)
        cur, _ = update_np_storage(cur, upd)
        matches, rep = apply_update_to_matches(
            cur, matches, upd, units, p, cover, ord_)
        pt, sdiag = sstep(pt, aj, dj)
        st, patch_dev, mdiag = mstep(pt, st, aj, dj)
        assert int(sdiag["overflow"]) == 0 and int(mdiag["overflow"]) == 0
        assert int(mdiag["count"]) == matches.count_matches(ord_)
        assert int(mdiag["removed_groups"]) == rep.removed_groups
        assert int(mdiag["patch_groups"]) == rep.patch.n_groups
        assert _tensor_rows(st.flatten(), p, cover, skel_cols, ord_) == \
            _table_rows(matches, ord_)


def _store_groups(tc):
    """``{skeleton row: {vertex: set row}}`` of the valid groups of a
    device store, set rows as stored (``set_cap`` entries, PAD tail)."""
    skel = np.asarray(tc.skeleton)
    valid = np.asarray(tc.valid)
    sets = {v: np.asarray(a) for v, a in tc.sets.items()}
    out = {tuple(skel[g].tolist()): {v: tuple(a[g].tolist()) for v, a in sets.items()}
           for g in np.nonzero(valid)[0]}
    assert len(out) == int(valid.sum()), "two valid groups share a skeleton"
    return out


def _host_groups(table, set_cap):
    """The same view of a host :class:`CompressedTable`, each set padded
    to ``set_cap`` with PAD."""
    out = {}
    for g in range(table.n_groups):
        row = {}
        for v, r in table.comp.items():
            vals = list(r.values[r.offsets[g]: r.offsets[g + 1]].tolist())
            row[v] = tuple(vals + [je.PAD] * (set_cap - len(vals)))
        out[tuple(table.skeleton[g].tolist())] = row
    return out


@pytest.mark.parametrize("pat,cover,batches", [
    ("q2_triangle", None, 20),      # 1 compressed vertex
    (PAT_3COMP, (0, 1), 20),        # 3 compressed vertices
])
def test_maintain_mega_step_store_matches_host(pat, cover, batches):
    """The megastep's store, group by group and set row by set row (PAD
    tails included), equals the host engine's compressed match table at
    every watermark of a streamed batch sequence."""
    from repro.core.incremental import apply_update_to_matches

    mesh, m = _mesh_and_m()
    g, p, ord_, cover, host = _maintenance_fixture(pat, seed=59, cover=cover)
    stats = GraphStats.of(g)
    tree = optimal_join_tree(p, cover, CostModel(cover, ord_, stats))
    prog = sharded.build_tree_program(tree, cover, ord_)
    units = minimum_unit_decomposition(p, cover)
    storage = build_np_storage(g, m)
    pt = _shard_input(sharded.stack_partitions(storage, CAPS), mesh)
    out, _ = sharded.make_list_step(prog, mesh, CAPS)(pt)
    store_caps = sharded.match_caps(p, cover, ord_, stats, CAPS)
    st, idiag = sharded.make_init_store_step(prog, mesh, CAPS, store_caps)(out)
    assert int(idiag["overflow"]) == 0
    ucaps = sharded.unit_table_caps(units, cover, ord_, stats, CAPS)
    carry, _ = sharded.make_unit_refresh_step(prog, units, mesh, CAPS, ucaps)(pt)
    spec = sharded.MaintainSpec(name="p", prog=prog, units=tuple(units),
                                store=store_caps, unit_caps=ucaps)
    mega = sharded.make_maintain_mega_step([spec], mesh, CAPS)
    sstep = sharded.make_storage_update_step(mesh, CAPS,
                                             sharded.UpdateShapes(n_add=3, n_del=3))
    skel_cols = prog.nodes[prog.root].skel_cols
    matches = host.state.matches
    assert tuple(matches.skeleton_cols) == tuple(skel_cols)
    set_cap = store_caps.set_cap
    assert _store_groups(st.flatten()) == \
        _host_groups(matches, set_cap)

    stores, carries = {"p": st}, {"p": carry}
    rng = np.random.default_rng(61)
    cur = storage
    for b in range(batches):
        add, dele = _sample_batch(cur.graph, rng, 3, 30)
        upd = GraphUpdate(delete=dele, add=add)
        cur, _ = update_np_storage(cur, upd)
        matches, _ = apply_update_to_matches(cur, matches, upd, units, p, cover, ord_)
        aj, dj = jnp.asarray(add, jnp.int32), jnp.asarray(dele, jnp.int32)
        pt, sdiag = sstep(pt, aj, dj)
        stores, _, carries, mdiag = mega(pt, stores, carries, sdiag["part_dirty"],
                                         aj, dj)
        assert int(sdiag["overflow"]) == 0 and int(mdiag["p"]["overflow"]) == 0
        assert int(mdiag["p"]["count"]) == matches.count_matches(ord_)
        assert _store_groups(stores["p"].flatten()) == \
            _host_groups(matches, set_cap), f"batch {b}: store drift"


def test_maintain_step_store_overflow_is_counted():
    """A store too small for the running match set reports overflow in
    diag — never a silent truncation."""
    mesh, m = _mesh_and_m()
    g, p, ord_, cover, _ = _maintenance_fixture("q2_triangle", seed=43)
    stats = GraphStats.of(g)
    tree = optimal_join_tree(p, cover, CostModel(cover, ord_, stats))
    prog = sharded.build_tree_program(tree, cover, ord_)
    storage = build_np_storage(g, m)
    pt = _shard_input(sharded.stack_partitions(storage, CAPS), mesh)
    out, _ = sharded.make_list_step(prog, mesh, CAPS)(pt)
    tiny = sharded.StoreCaps(group_cap=2, set_cap=2)
    _, idiag = sharded.make_init_store_step(prog, mesh, CAPS, tiny)(out)
    assert int(idiag["overflow"]) > 0


def test_update_step_matches_host():
    mesh, m = _mesh_and_m()
    g, pat, ord_, cover, tree, prog = _setup("q1_square")
    units = minimum_unit_decomposition(pat, cover)
    storage = build_np_storage(g, m)

    rng = np.random.default_rng(3)
    ecur = g.edges()
    dele = ecur[rng.choice(ecur.shape[0], size=3, replace=False)]
    existing = set(map(tuple, ecur.tolist()))
    add = set()
    while len(add) < 3:
        a, b = int(rng.integers(36)), int(rng.integers(36))
        if a != b and (min(a, b), max(a, b)) not in existing:
            add.add((min(a, b), max(a, b)))
    add = np.array(sorted(add))
    upd = GraphUpdate(delete=dele, add=add)

    storage2, _ = update_np_storage(storage, upd)
    patch_host = nav_join_patch(storage2, units, pat, cover, ord_, add)
    _, pht = patch_host.decompress(ord_)

    pt = _shard_input(sharded.stack_partitions(storage, CAPS), mesh)
    step = sharded.make_update_step(prog, units, mesh, CAPS,
                                    sharded.UpdateShapes(n_add=3, n_del=3))
    pt2, patch, diag = step(pt, jnp.asarray(add, jnp.int32), jnp.asarray(dele, jnp.int32))
    assert int(diag["overflow"]) == 0

    # storage delta == rebuild of Φ(d')
    rebuilt = build_np_storage(storage2.graph, m)
    for j in range(m):
        ehi = np.asarray(pt2.edge_hi)[j]
        elo = np.asarray(pt2.edge_lo)[j]
        got = set((int(a), int(b)) for a, b in zip(ehi, elo) if a >= 0)
        want = set((int(c >> 32), int(c & 0xFFFFFFFF)) for c in rebuilt.parts[j].codes)
        assert got == want

    # patch == host Nav-join
    skel = np.asarray(patch.skeleton).reshape(-1, patch.skeleton.shape[-1])
    valid = np.asarray(patch.valid).reshape(-1)
    sets = {k: jnp.array(np.asarray(v).reshape(-1, v.shape[-1]))
            for k, v in patch.sets.items()}
    t = je.CompTensors(skeleton=jnp.array(skel), valid=jnp.array(valid), sets=sets)
    full_skel = tuple(c for c in sorted(cover) if c in set(pat.vertices))
    back = je.comp_to_host(t, pat, cover, full_skel)
    _, jt = back.decompress(ord_)
    assert set(map(tuple, pht.tolist())) == set(map(tuple, jt.tolist()))


def test_wcoj_seed_mask_is_endpoints_and_common_neighbours():
    """The delta WCOJ patch seeds exactly the inserted endpoints and
    their common d'-neighbours — every possible anchor of a new match,
    and no other vertex (a hub next to one endpoint stays unseeded)."""
    from jax.sharding import PartitionSpec as P

    g = random_graph(36, 90, seed=11)
    mesh = jax.make_mesh((1,), ("data",))
    edges = set(map(tuple, g.edges().tolist()))
    add = np.array([(0, 1), (2, 3), (5, 9)], np.int32)
    add = add[[tuple(e) not in edges for e in add]]
    g2 = g.apply_update(GraphUpdate.make(add=add))
    pt2 = sharded.stack_partitions(build_np_storage(g2, 1), CAPS)
    batch = np.vstack([add, np.full((2, 2), -1, np.int32)])   # padded rows

    def body(p):
        p = jax.tree.map(lambda x: x[0], p)
        return sharded._wcoj_seed_mask(p, jnp.asarray(batch), ("data",))[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(sharded.partition_specs(mesh),),
                       out_specs=P("data"), check_vma=False)
    hit = np.asarray(fn(pt2))[0]
    verts = np.asarray(pt2.vertices)[0]
    got = set(verts[hit & (verts >= 0)].tolist())
    nbr = {v: set(g2.indices[g2.indptr[v]:g2.indptr[v + 1]].tolist())
           for v in range(g2.n)}
    want = set()
    for a, b in add.tolist():
        want |= {a, b} | (nbr[a] & nbr[b])
    assert got == want
