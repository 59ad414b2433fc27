"""Whole join-tree programs under a ``jax.sharding`` mesh.

The mesh's devices hold one NP partition each (leading array dim = flat
device index = partition id; the partition function is ``h(v) = v mod
M``). Two jitted SPMD steps execute the paper's two stages:

- :func:`make_list_step` — stage 1, the initial calculation: every
  device lists its anchored unit matches locally (disjoint & complete by
  Lemma 3.1), then each CC-join of the tree program redistributes
  groups by join-key ownership (all-gather + hash filter) and joins
  co-located tensors with :func:`repro.dist.jax_engine.ccjoin_local`.
- :func:`make_update_step` — stage 2, a batch update: the (small,
  replicated) edge batch drives the paper's candidate-restricted
  incremental shuffle (Alg. 4 C1–C3, ``mode="delta"``): the candidate
  vertex set (delta endpoints ∪ their d'-neighborhoods) is gathered
  from the partition centers, the NP membership rule ``(a,b) ∈ E_j ⇔
  h(a)=j ∨ h(b)=j ∨ ∃z ∈ CN(a,b): h(z)=j`` is re-evaluated only for
  d'-edges incident to the delta, and the stored partitions are patched
  in place — per-batch cost scales with ``|δ|``, not ``|E(d)|``.
  ``mode="full"`` keeps the original exact oracle (full global
  adjacency gather + membership recompute); the two byte-match, and the
  Nav-join patch chains (§VI-B, Thm. 6.1 dedup) run on the updated
  partitions either way.

Both steps execute the *same* :class:`~repro.core.plan.UnitPlan` /
:class:`~repro.core.plan.JoinPlan` IR as the host engine and report
capacity overflow through explicit counters in their ``diag`` dict —
never by silent truncation.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.navjoin import left_deep_order
from repro.core.pattern import Pattern, R1Unit
from repro.core.plan import JoinPlan, UnitPlan, WcojPlan, build_unit_plan
from repro.core.storage import NPStorage
from repro.planner.lowering import TreeNode, TreeProgram, build_tree_program
from repro.planner.sizing import StoreCaps, match_caps, unit_table_caps

from . import jax_engine as je
from .jax_engine import PAD, CompTensors, EngineCaps, PaddedPartition, _BIG, _I32

__all__ = [
    "TreeNode",
    "TreeProgram",
    "build_tree_program",
    "stack_partitions",
    "partition_specs",
    "ddsl_input_specs",
    "make_list_step",
    "UpdateShapes",
    "make_update_step",
    "make_storage_update_step",
    "make_patch_step",
    "MatchStore",
    "StoreCaps",
    "match_caps",
    "match_specs",
    "stack_matches",
    "UnitCarry",
    "unit_plan_registry",
    "unit_table_caps",
    "unit_carry_specs",
    "make_unit_refresh_step",
    "make_init_store_step",
    "make_wcoj_list_step",
    "make_wcoj_init_store_step",
    "make_maintain_step",
    "MaintainSpec",
    "make_maintain_mega_step",
]


# ---------------------------------------------------------------------------
# Tree programs: TreeNode / TreeProgram / build_tree_program now live in
# repro.planner.lowering (the compiler's JAX-free lowering stage) and are
# re-imported above — this module keeps them in __all__ for its callers.
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Input pytrees
# ---------------------------------------------------------------------------

def stack_partitions(storage: NPStorage, caps: EngineCaps) -> PaddedPartition:
    """Pad every partition and stack along a leading device axis [M, ...]."""
    pads = [je.pad_partition(p, caps) for p in storage.parts]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *pads)


def _flat_axes(mesh: Mesh):
    axes = tuple(mesh.axis_names)
    return axes if len(axes) > 1 else axes[0]


def partition_specs(mesh: Mesh) -> PaddedPartition:
    """PartitionSpecs sharding the leading (device/partition) dim."""
    spec = P(_flat_axes(mesh))
    return PaddedPartition(vertices=spec, center=spec, deg=spec,
                           adj=spec, edge_hi=spec, edge_lo=spec)


def ddsl_input_specs(caps: EngineCaps, m: int) -> PaddedPartition:
    """ShapeDtypeStructs of the stacked input (for dry-run lowering)."""
    sd = jax.ShapeDtypeStruct
    return PaddedPartition(
        vertices=sd((m, caps.v_cap), jnp.int32),
        center=sd((m, caps.v_cap), jnp.bool_),
        deg=sd((m, caps.v_cap), jnp.int32),
        adj=sd((m, caps.v_cap, caps.deg_cap), jnp.int32),
        edge_hi=sd((m, caps.e_cap), jnp.int32),
        edge_lo=sd((m, caps.e_cap), jnp.int32),
    )


def _comp_spec(pattern: Pattern, cover: Sequence[int], spec) -> CompTensors:
    comp = sorted(set(pattern.vertices) - set(cover))
    return CompTensors(skeleton=spec, valid=spec, sets={v: spec for v in comp})


# ---------------------------------------------------------------------------
# Distributed CC-join: all-gather + join-key ownership + local join
# ---------------------------------------------------------------------------

def _mesh_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))


def _my_index(mesh: Mesh) -> jnp.ndarray:
    idx = jnp.int32(0)
    for ax in mesh.axis_names:
        idx = idx * mesh.shape[ax] + lax.axis_index(ax)
    return idx


def _owner_of(skel: jnp.ndarray, key_idx: Sequence[int], m: int) -> jnp.ndarray:
    """Deterministic join-key → device hash (same on every device)."""
    h = jnp.zeros(skel.shape[0], _I32)
    for j in key_idx:
        h = h * jnp.int32(1000003) + skel[:, j]
    return ((h % m) + m) % m


def _gather_groups(tc: CompTensors, axes) -> CompTensors:
    def g(x):
        y = lax.all_gather(x, axes)
        return y.reshape((-1,) + x.shape[1:])

    return jax.tree.map(g, tc)


def _compact_groups(tc: CompTensors, ok: jnp.ndarray, cap: int):
    """Pack the ``ok`` groups into ``cap`` slots; count drops."""
    dest, valid, dropped = je._compact_index(ok, cap)

    def pack(arr):
        return jnp.full((cap + 1,) + arr.shape[1:], PAD, arr.dtype).at[dest].set(arr)[:cap]

    skel = pack(tc.skeleton)
    sets = {v: pack(a) for v, a in tc.sets.items()}
    return CompTensors(skeleton=skel, valid=valid, sets=sets), dropped


def _dist_join(tcA: CompTensors, tcB: CompTensors, plan: JoinPlan,
               caps: EngineCaps, mesh: Mesh):
    """Redistribute both sides by join-key ownership, then join locally.

    Every input group lives on exactly one device (units by the
    anchor→center rule, join outputs by this very ownership rule), so
    the all-gather + hash-filter keeps exactly one global copy of each
    group and the local joins partition the global join 1:1.
    """
    axes = tuple(mesh.axis_names)
    m = _mesh_size(mesh)
    me = _my_index(mesh)
    gA = _gather_groups(tcA, axes)
    gB = _gather_groups(tcB, axes)
    okA = gA.valid & (_owner_of(gA.skeleton, plan.key_left_idx, m) == me)
    okB = gB.valid & (_owner_of(gB.skeleton, plan.key_right_idx, m) == me)
    tA2, o1 = _compact_groups(gA, okA, caps.group_cap)
    tB2, o2 = _compact_groups(gB, okB, caps.group_cap)
    out, o3 = je.ccjoin_local(tA2, tB2, plan, caps)
    return out, o1 + o2 + o3


# ---------------------------------------------------------------------------
# Stage 1: distributed initial calculation
# ---------------------------------------------------------------------------

def make_list_step(prog: TreeProgram, mesh: Mesh, caps: EngineCaps):
    """Jitted SPMD step: stacked partitions → (root CompTensors, diag)."""
    axes = tuple(mesh.axis_names)
    ax = _flat_axes(mesh)
    root_node = prog.nodes[prog.root]

    def body(pt_st: PaddedPartition):
        pt = jax.tree.map(lambda x: x[0], pt_st)
        ovf = jnp.int32(0)
        res: List[CompTensors] = []
        for node in prog.nodes:
            if node.unit_plan is not None:
                tbl, valid, o1 = je.unit_list(pt, node.unit_plan, caps)
                tc, _, o2 = je.compress_plain(tbl, valid, node.unit_plan.cols,
                                              prog.cover, caps)
                ovf = ovf + o1 + o2
            else:
                tc, o = _dist_join(res[node.left], res[node.right],
                                   node.join_plan, caps, mesh)
                ovf = ovf + o
            res.append(tc)
        root = res[prog.root]
        diag = {
            "overflow": lax.psum(ovf, axes),
            "matches_lower_bound": lax.psum(jnp.sum(root.valid.astype(_I32)), axes),
        }
        return jax.tree.map(lambda x: x[None], root), diag

    out_specs = (_comp_spec(root_node.pattern, prog.cover, P(ax)),
                 {"overflow": P(), "matches_lower_bound": P()})
    fn = jax.shard_map(body, mesh=mesh, in_specs=(partition_specs(mesh),),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Stage 2: distributed batch update + Nav-join patch
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UpdateShapes:
    """Static batch-update shape model (|E_a|, |E_d| are compile-time).

    ``cand_cap`` / ``cedge_cap`` bound the candidate vertex and
    candidate edge sets of the delta-restricted update (``mode="delta"``
    of :func:`make_storage_update_step`). ``None`` derives bounds that
    can never overflow: at most ``2·(n_add + n_del)`` C1 endpoints, each
    contributing ≤ ``deg_cap`` neighbors / candidate edges. Tighter
    explicit values trade memory for a counted overflow risk.
    """

    n_add: int
    n_del: int
    cand_cap: Optional[int] = None
    cedge_cap: Optional[int] = None

    def delta_caps(self, caps: EngineCaps, m: int) -> Tuple[int, int, int]:
        """Resolved ``(c1_cap, cand_cap, cedge_cap)`` for a mesh of ``m``."""
        c1_cap = max(2 * (self.n_add + self.n_del), 1)
        nv_glob = m * caps.v_cap
        cand = self.cand_cap if self.cand_cap is not None else min(
            nv_glob, c1_cap * (caps.deg_cap + 1))
        cedge = self.cedge_cap if self.cedge_cap is not None else c1_cap * caps.deg_cap
        return c1_cap, max(cand, 1), max(cedge, 1)

    @staticmethod
    def from_estimator(n_add: int, n_del: int, stats, caps: EngineCaps,
                       m: int, safety: float = 8.0) -> "UpdateShapes":
        """Candidate caps sized from the §IV-D degree statistics.

        The never-overflow derivation bounds every C1 endpoint by
        ``deg_cap`` neighbors — the *maximum* degree with growth
        headroom, far above what a typical delta touches on a power-law
        graph. The endpoints of a random edge operation follow the
        *size-biased* degree distribution, whose mean is
        ``E[deg²]/E[deg] = T(2)/T(1)`` over the empirical histogram
        (:class:`repro.core.estimator.GraphStats`), so the expected
        candidate set is ``|C1|·(1 + T(2)/T(1))``. ``safety`` scales
        that expectation; the result is clamped to the never-overflow
        bound (estimator sizing can only shrink the psum payload, never
        grow it). Degenerate stats (empty graph) fall back to the
        never-overflow derivation, and any overflow that a too-small
        cap does cause is still counted in ``diag`` — never silent.
        """
        c1 = max(2 * (n_add + n_del), 1)
        t1 = stats.t_term(1)
        if t1 <= 0.0:
            return UpdateShapes(n_add=n_add, n_del=n_del)
        sb_deg = max(stats.t_term(2) / t1, 1.0)
        exp_nbrs = int(np.ceil(safety * sb_deg))
        nv_glob = m * caps.v_cap
        cand_no = min(nv_glob, c1 * (caps.deg_cap + 1))
        cedge_no = c1 * caps.deg_cap
        return UpdateShapes(
            n_add=n_add, n_del=n_del,
            cand_cap=max(min(cand_no, c1 * (exp_nbrs + 1)), 1),
            cedge_cap=max(min(cedge_no, c1 * exp_nbrs), 1),
        )


@dataclasses.dataclass(frozen=True)
class _ChainPlan:
    seed_plan: UnitPlan
    steps: Tuple[Tuple[UnitPlan, JoinPlan], ...]
    skel_pairs: Tuple[Tuple[int, int], ...]       # Thm 6.1 dedup, skeleton edges
    comp_pairs: Tuple[Tuple[int, int], ...]       # (comp label, skeleton col idx)


def _chain_plans(units: Sequence[R1Unit], pattern: Pattern,
                 cover: Tuple[int, ...], ord_) -> Tuple[_ChainPlan, ...]:
    full_skel = tuple(c for c in cover if c in set(pattern.vertices))
    sidx = {c: j for j, c in enumerate(full_skel)}
    plans = []
    for i, qi in enumerate(units):
        order = left_deep_order(units, qi, cover)
        seed = build_unit_plan(qi.pattern, qi.anchor_in(cover), ord_)
        steps = []
        cur = qi.pattern
        for qk in order[1:]:
            up = build_unit_plan(qk.pattern, qk.anchor_in(cover), ord_)
            jp = JoinPlan.make(cur, qk.pattern, cover, ord_)
            steps.append((up, jp))
            cur = cur.union(qk.pattern)
        skel_pairs, comp_pairs = set(), set()
        for qj in units[:i]:
            for a, b in qj.pattern.edges:
                if a in sidx and b in sidx:
                    skel_pairs.add((sidx[a], sidx[b]))
                elif a in sidx:
                    comp_pairs.add((b, sidx[a]))
                else:  # b in skeleton (every pattern edge has a cover endpoint)
                    comp_pairs.add((a, sidx[b]))
        plans.append(_ChainPlan(seed_plan=seed, steps=tuple(steps),
                                skel_pairs=tuple(sorted(skel_pairs)),
                                comp_pairs=tuple(sorted(comp_pairs))))
    return tuple(plans)


def _edge_in(lo: jnp.ndarray, hi: jnp.ndarray, ea: jnp.ndarray, eb: jnp.ndarray):
    """Membership of (lo, hi) pairs in a small replicated edge list."""
    if ea.shape[0] == 0:
        return jnp.zeros(lo.shape, bool)
    return jnp.any((lo[..., None] == ea) & (hi[..., None] == eb), axis=-1)


def _pair_compat(cur: CompTensors, u: int, w: int, ord_set) -> jnp.ndarray:
    """``[G, S_u, S_w]`` mask: value pairs compatible under injectivity+ord."""
    a = cur.sets[u]
    b = cur.sets[w]
    ok = (a >= 0)[:, :, None] & (b >= 0)[:, None, :] & (a[:, :, None] != b[:, None, :])
    if (u, w) in ord_set:
        ok &= a[:, :, None] < b[:, None, :]
    if (w, u) in ord_set:
        ok &= a[:, :, None] > b[:, None, :]
    return ok


def _purge_nonparticipating(cur: CompTensors, comp_labels, ord_, set_cap: int):
    """Drop set values participating in no full compressed-vertex assignment.

    Needed so the cross-chain union of sets equals the host's union of
    row-derived values when patch chains share a skeleton group. Exact
    for ≤3 compressed vertices: pairwise partner existence for 2, and
    for 3 the triple-feasibility test ``∃(b,c): ok(a,b) ∧ ok(a,c) ∧
    ok(b,c)`` evaluated as a boolean matmul over the third set (O(G·S³)
    work, O(G·S²) memory). For ≥4 every 3-subset containing the vertex
    is required to be feasible (3-consistency) — a sound
    over-approximation, strictly tighter than pairwise.
    """
    if len(comp_labels) < 2:
        return cur
    ord_set = set(ord_)
    pair: Dict[Tuple[int, int], jnp.ndarray] = {}

    def pok(u, w):
        if (u, w) not in pair:
            pair[(u, w)] = _pair_compat(cur, u, w, ord_set)
            pair[(w, u)] = jnp.swapaxes(pair[(u, w)], 1, 2)
        return pair[(u, w)]

    keeps = {}
    for u in comp_labels:
        others = [w for w in comp_labels if w != u]
        keep = cur.sets[u] >= 0
        if len(others) == 1:
            keep &= jnp.any(pok(u, others[0]), axis=2)
        else:
            # Triple feasibility for every pair of siblings: ∃(b ∈ S_w,
            # c ∈ S_x) with all three pairwise constraints satisfied.
            for i, w in enumerate(others):
                for x in others[i + 1:]:
                    # r[g,a,b] = ∃c: ok(a,c) ∧ ok(b,c) — contraction over x.
                    r = jnp.einsum("gac,gbc->gab", pok(u, x).astype(_I32),
                                   pok(w, x).astype(_I32)) > 0
                    keep &= jnp.any(pok(u, w) & r, axis=2)
        keeps[u] = keep
    valid = cur.valid
    sets = dict(cur.sets)
    for u in comp_labels:
        packed, counts = je._filter_set_rows(cur.sets[u], keeps[u] & valid[:, None], set_cap)
        sets[u] = packed
        valid = valid & (counts > 0)
    return CompTensors(skeleton=cur.skeleton, valid=valid, sets=sets)


# Regrouping rows by identical skeleton (unioning per-vertex sets) is
# now the engine primitive :func:`repro.dist.jax_engine.merge_groups`,
# shared by the patch merge below and the match-store maintenance.


def _storage_update_body(pt: PaddedPartition, add: jnp.ndarray, dele: jnp.ndarray,
                         mesh: Mesh, caps: EngineCaps, ushapes: UpdateShapes):
    """One device's half of Alg. 4: ``Φ(d)_me → Φ(d')_me`` (+ overflow).

    Pattern-independent — compiled once per (mesh, caps, shapes) and
    shared by every registered pattern of a streaming service.
    """
    axes = tuple(mesh.axis_names)
    m = _mesh_size(mesh)
    me = _my_index(mesh)
    nv_glob = m * caps.v_cap
    chunk = 64 if nv_glob % 64 == 0 else caps.v_cap
    n_chunks = nv_glob // chunk
    ovf = jnp.int32(0)

    # ---- exact global adjacency from partition centers --------------
    mine = pt.center & (pt.vertices >= 0)
    ovf = ovf + jnp.sum((mine & (pt.vertices >= nv_glob)).astype(_I32))
    vdest = jnp.where(mine & (pt.vertices < nv_glob), pt.vertices, nv_glob)
    contrib = jnp.zeros((nv_glob + 1, caps.deg_cap), _I32).at[vdest].set(pt.adj + 1)
    gn = lax.psum(contrib[:nv_glob], axes) - 1           # PAD where absent
    gm = jnp.where(gn < 0, _BIG, gn)                     # [NV, deg_cap]

    # ---- apply the replicated batch update --------------------------
    add = add.astype(_I32)
    dele = dele.astype(_I32)
    gmD = jnp.concatenate([gm, jnp.full((1, caps.deg_cap), _BIG, _I32)], axis=0)
    for t in range(ushapes.n_del):
        a, b = dele[t, 0], dele[t, 1]
        for u, w in ((a, b), (b, a)):
            us = jnp.where((u >= 0) & (u < nv_glob), u, nv_glob)
            row = gmD[us]
            gmD = gmD.at[us].set(jnp.where(row == w, _BIG, row))
    for t in range(ushapes.n_add):
        a, b = add[t, 0], add[t, 1]
        oob = (a >= nv_glob) | (b >= nv_glob)
        ovf = ovf + oob.astype(_I32)
        # Negative endpoints mark padding rows (fixed-size batches):
        # route the whole row to the dump slot, uncounted.
        bad = oob | (a < 0) | (b < 0)
        for u, w in ((a, b), (b, a)):
            us = jnp.where(bad | (u < 0) | (u >= nv_glob), nv_glob, u)
            row = gmD[us]
            # Idempotent insert: the host rejects already-present
            # edges with an exception; a jitted step can't, so a
            # duplicate (or twice-listed) add becomes a no-op here
            # instead of corrupting the adjacency multiset.
            present = jnp.any(row == w)
            free = row == _BIG
            has = jnp.any(free)
            ovf = ovf + ((~has) & (~present) & (~bad)).astype(_I32)
            slot = jnp.argmax(free)
            ins = has & ~present & ~bad
            gmD = gmD.at[us, slot].set(jnp.where(ins, w, row[slot]))
    gm = jnp.sort(gmD[:nv_glob], axis=1)                 # valid prefix asc

    # ---- NP membership rule for my part (== rebuild of Φ(d')_me) ----
    def memb_chunk(ids):
        rv = gm[ids]                                     # [C, D] neighbors
        wvalid = rv != _BIG
        m1 = ((ids % m) == me)[:, None] | (wvalid & ((rv % m) == me))
        nw = gm[jnp.clip(rv, 0, nv_glob - 1)]            # [C, Dw, Du]
        zmask = wvalid & ((rv % m) == me)                # z ∈ N(v), h(z)=me
        eqz = nw[:, :, :, None] == rv[:, None, None, :]  # [C, Dw, Du, Dt]
        cond = jnp.any(jnp.any(eqz, axis=2) & zmask[:, None, :], axis=2)
        return (m1 | cond) & wvalid

    ids = jnp.arange(nv_glob).reshape(n_chunks, chunk)
    memb = lax.map(memb_chunk, ids).reshape(nv_glob, caps.deg_cap)

    inpart = jnp.any(memb, axis=1)
    vertices, vvalid, o = je._compact_vec(
        jnp.arange(nv_glob, dtype=_I32), inpart, caps.v_cap, fill=PAD)
    ovf = ovf + o
    vsafe = jnp.where(vertices >= 0, vertices, 0)
    ladj = jnp.where(memb[vsafe] & vvalid[:, None], gm[vsafe], _BIG)
    ladj = jnp.sort(ladj, axis=1)
    ldeg = jnp.sum((ladj != _BIG).astype(_I32), axis=1)
    ladj = jnp.where(ladj == _BIG, PAD, ladj)
    center = vvalid & (vertices % m == me)
    vv = jnp.broadcast_to(vertices[:, None], ladj.shape)
    e_ok = (ladj >= 0) & (ladj > vv)
    epairs = jnp.stack([vv.reshape(-1), ladj.reshape(-1)], axis=1)
    epacked, _, oe = je._compact_rows(epairs, e_ok.reshape(-1), caps.e_cap)
    ovf = ovf + oe
    pt2 = PaddedPartition(vertices=vertices, center=center, deg=ldeg,
                          adj=ladj, edge_hi=epacked[:, 0], edge_lo=epacked[:, 1])
    return pt2, ovf


def _delta_update_body(pt: PaddedPartition, add: jnp.ndarray, dele: jnp.ndarray,
                       mesh: Mesh, caps: EngineCaps, ushapes: UpdateShapes):
    """Candidate-restricted Alg. 4 (C1–C3): ``Φ(d)_me → Φ(d')_me`` from the
    delta alone.

    Instead of re-gathering the whole global adjacency and re-deriving
    NP membership for every vertex (the ``_storage_update_body``
    oracle), only the *candidate* state moves:

    - **C1** — endpoints of inserted/deleted edges (the only vertices
      whose neighborhoods change).
    - **C2** — ``C1 ∪ N_{d'}(C1)``: membership of an edge ``(v, w)``
      depends on ``CN(v, w)``, which can only change when ``v`` or ``w``
      lies in C1; evaluating the rule needs the adjacency rows of both
      endpoints of every affected edge. Rows are shuffled from their
      partition centers (one ``psum`` over ``[cand_cap, deg_cap]``, not
      ``[NV, deg_cap]``).
    - **C3** — the affected NP members: every d'-edge incident to C1.
      Their membership bit is re-evaluated against the candidate rows;
      all other stored edges keep their bit (their common neighborhoods
      are untouched), so the partition is patched in place.

    Byte-identical to the full-gather oracle (tested on randomized
    update streams); per-batch work scales with ``|δ|·deg_cap``, not
    ``|E(d)|``. The three phases run under the named scopes
    ``storage/candidates``, ``storage/membership`` and ``storage/patch``.
    """
    axes = tuple(mesh.axis_names)
    m = _mesh_size(mesh)
    me = _my_index(mesh)
    nv_glob = m * caps.v_cap
    c1_cap, cand_cap, cedge_cap = ushapes.delta_caps(caps, m)
    add = add.astype(_I32)
    dele = dele.astype(_I32)
    ovf = jnp.int32(0)

    # Out-of-bounds inserts are counted (and skipped) like the oracle;
    # negative endpoints mark padding rows of the fixed-size batch.
    ovf = ovf + jnp.sum(jnp.any(add >= nv_glob, axis=1).astype(_I32))

    with jax.named_scope("storage/candidates"):
        # ---- C1: endpoints of the delta (replicated) --------------------
        ends = jnp.concatenate([add.reshape(-1), dele.reshape(-1)])
        e_ok = (ends >= 0) & (ends < nv_glob)
        c1_t, c1_valid, o1 = je.dedup_rows(ends[:, None], e_ok, c1_cap)
        c1 = c1_t[:, 0]
        ovf = ovf + o1

        # ---- candidate rows: C1 first, then C = C1 ∪ N_d'(C1) -----------
        rows1 = lax.psum(je.center_adj_contrib(pt, c1, c1_valid), axes) - 1
        rows1, _ = je.apply_edge_delta_rows(c1, rows1, add, dele, nv_glob,
                                            count_overflow=False)
        cids = jnp.concatenate([c1, rows1.reshape(-1)])
        c_ok = cids >= 0
        cand_t, cand_valid, o2 = je.dedup_rows(cids[:, None], c_ok, cand_cap)
        cand = cand_t[:, 0]
        ovf = ovf + o2

        rows_c = lax.psum(je.center_adj_contrib(pt, cand, cand_valid), axes) - 1
        rows_c, o3 = je.apply_edge_delta_rows(cand, rows_c, add, dele, nv_glob)
        ovf = ovf + o3

        # ---- candidate edges: every d'-edge incident to C1 --------------
        i1, h1 = je.lookup_sorted(cand, c1)
        nb = jnp.where(h1[:, None], rows_c[i1], PAD)
        vv = jnp.broadcast_to(c1[:, None], nb.shape)
        pair_ok = c1_valid[:, None] & (nb >= 0)
        pairs = jnp.stack([jnp.minimum(vv, nb).reshape(-1),
                           jnp.maximum(vv, nb).reshape(-1)], axis=1)
        ce, ce_valid, o4 = je.dedup_rows(pairs, pair_ok.reshape(-1), cedge_cap)
        ovf = ovf + o4

    with jax.named_scope("storage/membership"):
        # ---- NP membership rule over the candidate rows -----------------
        ia, ha = je.lookup_sorted(cand, ce[:, 0])
        ib, hb = je.lookup_sorted(cand, ce[:, 1])
        ra = jnp.where((ce_valid & ha)[:, None], rows_c[ia], PAD)
        rb = jnp.where((ce_valid & hb)[:, None], rows_c[ib], PAD)
        direct = ((ce[:, 0] % m) == me) | ((ce[:, 1] % m) == me)
        zmine = (ra >= 0) & ((ra % m) == me)                  # z ∈ N(a), h(z)=me
        zcommon = jnp.any((ra[:, :, None] == rb[:, None, :]) & (rb >= 0)[:, None, :],
                          axis=2)                             # z ∈ N(a) ∩ N(b)
        member = ce_valid & (direct | jnp.any(zmine & zcommon, axis=1))

    with jax.named_scope("storage/patch"):
        # ---- patch the stored partition in place ------------------------
        # Every stored edge whose membership may change is either deleted
        # or a candidate edge; drop those and re-insert candidates that
        # (still or newly) satisfy the rule.
        bad_d = (dele[:, 0] < 0) | (dele[:, 1] < 0)
        d_hi = jnp.where(bad_d, PAD, jnp.minimum(dele[:, 0], dele[:, 1]))
        d_lo = jnp.where(bad_d, PAD, jnp.maximum(dele[:, 0], dele[:, 1]))
        probe_rows = jnp.concatenate([ce, jnp.stack([d_hi, d_lo], axis=1)], axis=0)
        # Re-sorting via dedup keeps the drop table lexicographic (the
        # edge_probe contract); the cap is exact, so nothing can drop.
        tbl, _, _ = je.dedup_rows(probe_rows, probe_rows[:, 0] >= 0,
                                  probe_rows.shape[0])
        pt2, o5 = je.patch_partition(
            pt, cand, cand_valid, tbl[:, 0], tbl[:, 1], ce[:, 0], ce[:, 1], member,
            nv_glob, m, me, caps, use_pallas=caps.use_pallas)
        ovf = ovf + o5

    counters = {
        "cand_vertices": jnp.sum(cand_valid.astype(_I32)),
        "cand_edges": jnp.sum(ce_valid.astype(_I32)),
        # Drops attributable to the candidate caps alone (cand_cap /
        # cedge_cap sizing) — callers that auto-fall back to the
        # never-overflow derivation gate on this, not on the summed
        # counter, which also carries e_cap/deg_cap/oob overflow no
        # cap resize can fix.
        "cand_overflow": o1 + o2 + o4,
    }
    return pt2, ovf, counters


def _patch_body(pt2: PaddedPartition, add: jnp.ndarray, prog: TreeProgram,
                chains: Tuple[_ChainPlan, ...], mesh: Mesh, caps: EngineCaps,
                unit_tables: Optional[Dict[Tuple, "UnitCarry"]] = None):
    """One device's Nav-join patch chains (Lemma 6.2 + Thm. 6.1) over the
    already-updated partition ``Φ(d')_me``.

    ``unit_tables`` (keyed by unit-pattern key) supplies this device's
    *carried* unit tables — the plain listing for seeds (re-filtered
    against this batch's ``E_a``) and the compressed form for chain
    steps — so a warm batch runs zero :func:`~repro.dist.jax_engine.unit_list`
    calls. Absent, every table is listed from ``Φ(d')_me`` as before;
    the two paths are bit-identical when the carry is fresh (the carry's
    refresh is exactly this listing).
    """
    axes = tuple(mesh.axis_names)
    m = _mesh_size(mesh)
    me = _my_index(mesh)
    pattern = prog.nodes[prog.root].pattern
    cover = prog.cover
    ord_t = prog.ord
    full_skel = tuple(c for c in cover if c in set(pattern.vertices))
    comp_labels = tuple(sorted(set(pattern.vertices) - set(cover)))
    add = add.astype(_I32)
    add_lo = jnp.minimum(add[:, 0], add[:, 1])
    add_hi = jnp.maximum(add[:, 0], add[:, 1])
    unit_cache: Dict[Tuple, Tuple[CompTensors, jnp.ndarray]] = {}

    def unit_table(up: UnitPlan):
        if unit_tables is not None:
            return unit_tables[up.pattern.key()].comp, jnp.int32(0)
        key = up.pattern.key()
        if key not in unit_cache:
            tbl, valid, o1 = je.unit_list(pt2, up, caps)
            tc, _, o2 = je.compress_plain(tbl, valid, up.cols, cover, caps)
            unit_cache[key] = (tc, o1 + o2)
        return unit_cache[key]

    chain_out: List[CompTensors] = []
    povf = jnp.int32(0)
    for chain in chains:
        if unit_tables is not None:
            uc = unit_tables[chain.seed_plan.pattern.key()]
            tbl = uc.tbl
            valid = uc.valid & je.require_edges_mask(
                tbl, chain.seed_plan.edge_cols, add)
            o1 = jnp.int32(0)
        else:
            tbl, valid, o1 = je.unit_list(pt2, chain.seed_plan, caps,
                                          require_edges=add)
        cur, _, o2 = je.compress_plain(tbl, valid, chain.seed_plan.cols,
                                       cover, caps)
        povf = povf + o1 + o2
        for up, jp in chain.steps:
            tck, o3 = unit_table(up)
            cur, o4 = _dist_join(cur, tck, jp, caps, mesh)
            povf = povf + o3 + o4
        # Thm. 6.1 dedup: drop matches mapping an earlier unit's edge
        # into E_a. Every pattern edge has a cover endpoint, so the
        # row filter factorizes over skeleton pairs / set values.
        valid = cur.valid
        sets = dict(cur.sets)
        for ia, ib in chain.skel_pairs:
            lo = jnp.minimum(cur.skeleton[:, ia], cur.skeleton[:, ib])
            hi = jnp.maximum(cur.skeleton[:, ia], cur.skeleton[:, ib])
            valid = valid & ~_edge_in(lo, hi, add_lo, add_hi)
        for v, iskel in chain.comp_pairs:
            vals = sets[v]
            sv = cur.skeleton[:, iskel][:, None]
            lo = jnp.minimum(vals, sv)
            hi = jnp.maximum(vals, sv)
            ok = (vals >= 0) & ~_edge_in(lo, hi, add_lo, add_hi)
            packed, counts = je._filter_set_rows(vals, ok & valid[:, None],
                                                 caps.set_cap)
            sets[v] = packed
            valid = valid & (counts > 0)
        cur = CompTensors(skeleton=cur.skeleton, valid=valid, sets=sets)
        cur = _purge_nonparticipating(cur, comp_labels, ord_t, caps.set_cap)
        chain_out.append(cur)
    for _, o in unit_cache.values():
        povf = povf + o

    # ---- merge chains: co-locate equal skeletons, union sets --------
    # Pairwise canonical-merge fold: every per-device chain table is
    # canonical within itself (unique skeletons, ascending sets), so the
    # L·m-way union folds through :func:`~repro.dist.jax_engine.merge_tables_dev`
    # — batched row sorts — instead of routing the whole
    # L·m·group_cap·set_cap (group, value) stream through one
    # multi-key sort that XLA:CPU serializes. Bit-identical union; under
    # group overflow the dropped-group identity follows the fold order.
    skel_idx = tuple(range(len(full_skel)))
    blocks: List[CompTensors] = []
    for tc in chain_out:
        g = _gather_groups(tc, axes)
        G = tc.skeleton.shape[0]
        for d in range(m):
            blk = jax.tree.map(lambda x: x[d * G:(d + 1) * G], g)
            mine = blk.valid & (_owner_of(blk.skeleton, skel_idx, m) == me)
            blocks.append(CompTensors(skeleton=blk.skeleton, valid=mine,
                                      sets=blk.sets))
    if len(blocks) == 1:
        blk = blocks[0]
        blocks.append(CompTensors(skeleton=blk.skeleton,
                                  valid=jnp.zeros_like(blk.valid),
                                  sets=blk.sets))
    patch, om = je.merge_tables_dev(blocks[0], blocks[1], caps.group_cap,
                                    caps.set_cap)
    for blk in blocks[2:]:
        patch, o = je.merge_tables_dev(patch, blk, caps.group_cap,
                                       caps.set_cap)
        om = om + o
    return patch, povf + om


def _run_storage_update(pt: PaddedPartition, add: jnp.ndarray, dele: jnp.ndarray,
                        mesh: Mesh, caps: EngineCaps, ushapes: UpdateShapes,
                        mode: str):
    """Dispatch one device's storage update body by ``mode``."""
    if mode == "full":
        pt2, ovf = _storage_update_body(pt, add, dele, mesh, caps, ushapes)
        return pt2, ovf, {}
    if mode == "delta":
        return _delta_update_body(pt, add, dele, mesh, caps, ushapes)
    raise ValueError(f"unknown update mode {mode!r} (expected 'delta' or 'full')")


def make_storage_update_step(mesh: Mesh, caps: EngineCaps, ushapes: UpdateShapes,
                             mode: str = "delta"):
    """Jitted SPMD step: (partitions, E_a, E_d) → (partitions', diag).

    The pattern-independent half of the batch update — a streaming
    service compiles it **once** and shares the resulting Φ(d') across
    every registered pattern's patch step. Assumes ``h(v) = v mod M``.

    ``mode="delta"`` (default) runs the candidate-restricted update
    (:func:`_delta_update_body`): per-batch cost scales with the delta,
    and ``diag`` additionally reports the per-batch ``cand_vertices`` /
    ``cand_edges`` set sizes. ``mode="full"`` keeps the exact
    full-gather oracle; the two byte-match.

    ``diag["part_dirty"]`` is a per-device ``[M]`` bool: whether this
    batch changed the partition's stored edge set. The canonical edge
    list determines the whole partition (adjacency, degrees, live
    centers), so an unchanged list proves every per-partition artifact
    — in particular the carried unit tables of
    :func:`make_maintain_step` — is still exact.
    """
    axes = tuple(mesh.axis_names)
    ax = _flat_axes(mesh)
    counter_keys = (("cand_vertices", "cand_edges", "cand_overflow")
                    if mode == "delta" else ())

    def body(pt_st: PaddedPartition, add: jnp.ndarray, dele: jnp.ndarray):
        pt = jax.tree.map(lambda x: x[0], pt_st)
        pt2, ovf, counters = _run_storage_update(pt, add, dele, mesh, caps,
                                                 ushapes, mode)
        changed = (jnp.any(pt2.edge_hi != pt.edge_hi)
                   | jnp.any(pt2.edge_lo != pt.edge_lo))
        diag = {
            "overflow": lax.psum(ovf, axes),
            "stored_edges": lax.psum(jnp.sum((pt2.edge_hi >= 0).astype(_I32)), axes),
            "part_dirty": changed[None],
            **counters,
        }
        return jax.tree.map(lambda x: x[None], pt2), diag

    diag_specs = {"overflow": P(), "stored_edges": P(), "part_dirty": P(ax),
                  **{k: P() for k in counter_keys}}
    out_specs = (partition_specs(mesh), diag_specs)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(partition_specs(mesh), P(), P()),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def make_patch_step(prog: TreeProgram, units: Sequence[R1Unit], mesh: Mesh,
                    caps: EngineCaps, unit_caps: Optional[StoreCaps] = None):
    """Jitted SPMD step: (updated partitions, E_a) → (patch, diag).

    The per-pattern half of the batch update: Nav-join patch chains over
    a Φ(d') produced by :func:`make_storage_update_step`.

    With ``unit_caps`` the step threads a persistent unit-table carry:
    signature becomes ``(pt2, carry, dirty, add) → (patch, carry',
    diag)`` where ``dirty`` is the storage step's per-device
    ``part_dirty`` flag — only dirty devices re-run ``unit_list``
    (behind a ``lax.cond``); everyone else joins against the carried
    tables. ``diag`` additionally reports ``unit_refreshes`` (devices
    refreshed this batch).
    """
    axes = tuple(mesh.axis_names)
    ax = _flat_axes(mesh)
    pattern = prog.nodes[prog.root].pattern
    chains = _chain_plans(units, pattern, prog.cover, prog.ord)

    if unit_caps is None:
        def body(pt2_st: PaddedPartition, add: jnp.ndarray):
            pt2 = jax.tree.map(lambda x: x[0], pt2_st)
            patch, povf = _patch_body(pt2, add, prog, chains, mesh, caps)
            diag = {
                "overflow": lax.psum(povf, axes),
                "patch_groups": lax.psum(jnp.sum(patch.valid.astype(_I32)), axes),
            }
            return jax.tree.map(lambda x: x[None], patch), diag

        out_specs = (_comp_spec(pattern, prog.cover, P(ax)),
                     {"overflow": P(), "patch_groups": P()})
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(partition_specs(mesh), P()),
                           out_specs=out_specs, check_vma=False)
        return jax.jit(fn)

    plans, names = unit_plan_registry(prog, units)
    carry_specs = unit_carry_specs(prog, units, mesh)

    def body_carry(pt2_st: PaddedPartition, carry_st, dirty_st,
                   add: jnp.ndarray):
        pt2 = jax.tree.map(lambda x: x[0], pt2_st)
        carry = jax.tree.map(lambda x: x[0], carry_st)
        dirty = dirty_st[0]
        carry2, rovf = lax.cond(
            dirty,
            lambda: _refresh_units(pt2, plans, prog.cover, caps, unit_caps),
            lambda: (carry, jnp.int32(0)))
        by_key = {k: carry2[n] for k, n in names.items()}
        patch, povf = _patch_body(pt2, add, prog, chains, mesh, caps,
                                  unit_tables=by_key)
        diag = {
            "overflow": lax.psum(povf + rovf, axes),
            "patch_groups": lax.psum(jnp.sum(patch.valid.astype(_I32)), axes),
            "unit_refreshes": lax.psum(dirty.astype(_I32), axes),
        }
        return (jax.tree.map(lambda x: x[None], patch),
                jax.tree.map(lambda x: x[None], carry2), diag)

    out_specs = (_comp_spec(pattern, prog.cover, P(ax)), carry_specs,
                 {"overflow": P(), "patch_groups": P(), "unit_refreshes": P()})
    fn = jax.shard_map(body_carry, mesh=mesh,
                       in_specs=(partition_specs(mesh), carry_specs,
                                 P(ax), P()),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def make_update_step(prog: TreeProgram, units: Sequence[R1Unit], mesh: Mesh,
                     caps: EngineCaps, ushapes: UpdateShapes,
                     mode: str = "delta"):
    """Jitted SPMD step: (partitions, E_a, E_d) → (partitions', patch, diag).

    Fused composition of :func:`make_storage_update_step` and
    :func:`make_patch_step` for single-pattern callers (``mode`` as in
    :func:`make_storage_update_step`). Assumes the modulo partition
    function ``h(v) = v mod M`` (the default
    :class:`~repro.core.storage.PartitionFn`).
    """
    axes = tuple(mesh.axis_names)
    ax = _flat_axes(mesh)
    pattern = prog.nodes[prog.root].pattern
    cover = prog.cover
    chains = _chain_plans(units, pattern, cover, prog.ord)
    counter_keys = (("cand_vertices", "cand_edges", "cand_overflow")
                    if mode == "delta" else ())

    def body(pt_st: PaddedPartition, add: jnp.ndarray, dele: jnp.ndarray):
        pt = jax.tree.map(lambda x: x[0], pt_st)
        pt2, ovf, counters = _run_storage_update(pt, add, dele, mesh, caps,
                                                 ushapes, mode)
        patch, povf = _patch_body(pt2, add, prog, chains, mesh, caps)
        diag = {
            "overflow": lax.psum(ovf + povf, axes),
            "patch_groups": lax.psum(jnp.sum(patch.valid.astype(_I32)), axes),
            "stored_edges": lax.psum(jnp.sum((pt2.edge_hi >= 0).astype(_I32)), axes),
            **counters,
        }
        return (jax.tree.map(lambda x: x[None], pt2),
                jax.tree.map(lambda x: x[None], patch), diag)

    out_specs = (partition_specs(mesh),
                 _comp_spec(pattern, cover, P(ax)),
                 {"overflow": P(), "patch_groups": P(), "stored_edges": P(),
                  **{k: P() for k in counter_keys}})
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(partition_specs(mesh), P(), P()),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Device-resident match store (§VI maintenance without leaving the mesh)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MatchStore:
    """One pattern's running compressed match set, sharded on the mesh.

    Same tensor layout as :class:`~repro.dist.jax_engine.CompTensors`
    with a leading device axis: ``skeleton [M, Gs, S]`` (PAD-filled),
    ``valid [M, Gs]``, ``sets`` mapping each compressed-vertex label to
    ``[M, Gs, set_cap]``. Groups are placed by the join-key ownership
    hash over the **full** skeleton (:func:`_owner_of` over all
    skeleton columns) — the same rule the per-pattern patch merge uses,
    so filter/merge/count of a batch are purely local per device.
    Skeletons are globally unique (each hashes to exactly one owner and
    each shard is regrouped), so flattening the shards yields a valid
    host :class:`~repro.core.vcbc.CompressedTable` on demand.
    """

    skeleton: jnp.ndarray
    valid: jnp.ndarray
    sets: Dict[int, jnp.ndarray]

    def as_comp(self) -> CompTensors:
        """View one device's shard (no leading axis) as plain tensors."""
        return CompTensors(skeleton=self.skeleton, valid=self.valid,
                           sets=dict(self.sets))

    def flatten(self) -> CompTensors:
        """All shards with the device axis folded away (``[M·G, ...]``)
        — the layout :func:`~repro.dist.jax_engine.comp_to_host`
        consumes. Valid because store skeletons are globally unique."""
        return CompTensors(
            skeleton=self.skeleton.reshape(-1, self.skeleton.shape[-1]),
            valid=self.valid.reshape(-1),
            sets={v: a.reshape(-1, a.shape[-1]) for v, a in self.sets.items()})


je._register(MatchStore, ("skeleton", "valid", "sets"))


# StoreCaps / match_caps moved to repro.planner.sizing (re-imported above).


def match_specs(mesh: Mesh, pattern: Pattern, cover: Sequence[int]) -> MatchStore:
    """PartitionSpecs sharding a store's leading (device) dim."""
    spec = P(_flat_axes(mesh))
    comp = sorted(set(pattern.vertices) - set(cover))
    return MatchStore(skeleton=spec, valid=spec, sets={v: spec for v in comp})


def _owner_rows_np(skel: np.ndarray, m: int) -> np.ndarray:
    """Host twin of :func:`_owner_of` (int32 wraparound semantics)."""
    h = np.zeros(skel.shape[0], np.int32)
    with np.errstate(over="ignore"):
        for j in range(skel.shape[1]):
            h = h * np.int32(1000003) + skel[:, j].astype(np.int32)
    return ((h.astype(np.int64) % m) + m) % m


def stack_matches(table, m: int, store: StoreCaps) -> MatchStore:
    """Shard a host :class:`~repro.core.vcbc.CompressedTable` into a
    stacked :class:`MatchStore` by full-skeleton ownership.

    The host-side init/restore path (the in-service path builds the
    store on device via :func:`make_init_store_step`). Store caps must
    hold every owner's shard — a misfit is a sizing error and raises
    instead of truncating, like :func:`~repro.dist.jax_engine.pad_partition`.
    """
    S = len(table.skeleton_cols)
    owner = _owner_rows_np(table.skeleton.astype(np.int64), m)
    comp_labels = sorted(int(v) for v in table.comp)
    shards = []
    for j in range(m):
        idx = np.nonzero(owner == j)[0]
        if idx.shape[0] > store.group_cap:
            raise ValueError(
                f"shard {j} holds {idx.shape[0]} groups > group_cap={store.group_cap}")
        skel = np.full((store.group_cap, S), PAD, np.int32)
        skel[: idx.shape[0]] = table.skeleton[idx]
        valid = np.zeros(store.group_cap, bool)
        valid[: idx.shape[0]] = True
        sets = {}
        for v in comp_labels:
            r = table.comp[v]
            arr = np.full((store.group_cap, store.set_cap), PAD, np.int32)
            for k, g in enumerate(idx):
                vals = r.values[r.offsets[g]: r.offsets[g + 1]]
                if vals.shape[0] > store.set_cap:
                    raise ValueError(
                        f"group set has {vals.shape[0]} values > set_cap={store.set_cap}")
                arr[k, : vals.shape[0]] = vals
            sets[v] = jnp.asarray(arr)
        shards.append(MatchStore(skeleton=jnp.asarray(skel),
                                 valid=jnp.asarray(valid), sets=sets))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *shards)


# ---------------------------------------------------------------------------
# Delta-maintained per-device unit-table carries (the §IV-D `fixed` killer)
# ---------------------------------------------------------------------------
#
# Every Nav-join patch chain step re-lists this device's full unit
# tables M_ac(q, d'_me) — work independent of the batch size, paid per
# pattern per batch. A unit table is a pure function of the partition's
# canonical edge list (Lemma 3.1 anchors units to centers), so the
# tables are *carried* across batches as persistent device buffers and
# refreshed — inside the fused step, behind a `lax.cond` — only when the
# storage step reports the partition dirty (`diag["part_dirty"]`).

@dataclasses.dataclass
class UnitCarry:
    """One unit plan's carried tables on one device: the plain listing
    (``tbl [match_cap, k]`` + ``valid``, what seed re-filtering needs)
    and its VCBC-compressed form (what chain-step CC-joins consume)."""

    tbl: jnp.ndarray
    valid: jnp.ndarray
    comp: CompTensors


je._register(UnitCarry, ("tbl", "valid", "comp"))


def unit_plan_registry(prog: TreeProgram, units: Sequence[R1Unit]):
    """Distinct unit plans of a pattern's patch chains.

    Returns ``(plans, names)``: ``plans`` maps a stable name (``u0``,
    ``u1``, … in sorted-key order) to the :class:`UnitPlan`, ``names``
    maps each unit-pattern key to its name. Seed plans and chain-step
    plans of the same unit shape share one entry — one carried table
    serves both roles.
    """
    pattern = prog.nodes[prog.root].pattern
    chains = _chain_plans(units, pattern, prog.cover, prog.ord)
    reg: Dict[Tuple, UnitPlan] = {}
    for chain in chains:
        for up in (chain.seed_plan, *(u for u, _ in chain.steps)):
            reg.setdefault(up.pattern.key(), up)
    names = {k: f"u{i}" for i, k in enumerate(sorted(reg))}
    return {names[k]: up for k, up in reg.items()}, names


# unit_table_caps moved to repro.planner.sizing (re-imported above).


def unit_carry_specs(prog: TreeProgram, units: Sequence[R1Unit],
                     mesh: Mesh) -> Dict[str, UnitCarry]:
    """PartitionSpecs sharding a carry pytree's leading (device) dim."""
    spec = P(_flat_axes(mesh))
    plans, _ = unit_plan_registry(prog, units)
    return {name: UnitCarry(tbl=spec, valid=spec,
                            comp=_comp_spec(up.pattern, prog.cover, spec))
            for name, up in plans.items()}


def _refresh_units(pt2: PaddedPartition, plans: Dict[str, UnitPlan],
                   cover: Tuple[int, ...], caps: EngineCaps,
                   ucaps: StoreCaps):
    """List + compress every registered unit plan from ``Φ(d')_me`` —
    the (cold) carry refresh, also the oracle a fresh carry must equal."""
    ccaps = dataclasses.replace(caps, group_cap=ucaps.group_cap,
                                set_cap=ucaps.set_cap)
    out: Dict[str, UnitCarry] = {}
    ovf = jnp.int32(0)
    for name in sorted(plans):
        up = plans[name]
        tbl, valid, o1 = je.unit_list(pt2, up, caps)
        tc, _, o2 = je.compress_plain(tbl, valid, up.cols, cover, ccaps)
        out[name] = UnitCarry(tbl=tbl, valid=valid, comp=tc)
        ovf = ovf + o1 + o2
    return out, ovf


def make_unit_refresh_step(prog: TreeProgram, units: Sequence[R1Unit],
                           mesh: Mesh, caps: EngineCaps, ucaps: StoreCaps):
    """Jitted SPMD step: Φ partitions → (unit-table carry, diag).

    The cold fill: a streaming backend runs it once at register/restore
    time; afterwards the fused maintain step keeps the carry fresh by
    refreshing only dirty devices. ``diag``: ``overflow``.
    """
    axes = tuple(mesh.axis_names)
    plans, _ = unit_plan_registry(prog, units)

    def body(pt_st: PaddedPartition):
        pt = jax.tree.map(lambda x: x[0], pt_st)
        carry, ovf = _refresh_units(pt, plans, prog.cover, caps, ucaps)
        diag = {"overflow": lax.psum(ovf, axes)}
        return jax.tree.map(lambda x: x[None], carry), diag

    out_specs = (unit_carry_specs(prog, units, mesh), {"overflow": P()})
    fn = jax.shard_map(body, mesh=mesh, in_specs=(partition_specs(mesh),),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def make_init_store_step(prog: TreeProgram, mesh: Mesh, caps: EngineCaps,
                         store: StoreCaps):
    """Jitted SPMD step: (root CompTensors from the list step) →
    (:class:`MatchStore`, diag).

    Redistributes the initial listing's groups by full-skeleton
    ownership (the store placement rule), regroups each shard into
    canonical form, and counts matches on device — the initial match
    set never visits the host. ``diag`` carries ``count``,
    ``store_groups`` and ``overflow``.
    """
    axes = tuple(mesh.axis_names)
    ax = _flat_axes(mesh)
    m = _mesh_size(mesh)
    root = prog.nodes[prog.root]
    n_s = len(root.skel_cols)

    def body(tc_st: CompTensors):
        tc = jax.tree.map(lambda x: x[0], tc_st)
        me = _my_index(mesh)
        g = _gather_groups(tc, axes)
        mine = g.valid & (_owner_of(g.skeleton, tuple(range(n_s)), m) == me)
        st, ovf = je.merge_groups(g.skeleton, mine, g.sets,
                                  store.group_cap, store.set_cap)
        cnt = je.count_matches_dev(st, root.skel_cols, prog.ord)
        diag = {
            "count": lax.psum(cnt, axes),
            "store_groups": lax.psum(jnp.sum(st.valid.astype(_I32)), axes),
            "overflow": lax.psum(ovf, axes),
        }
        out = MatchStore(skeleton=st.skeleton, valid=st.valid, sets=st.sets)
        return jax.tree.map(lambda x: x[None], out), diag

    out_specs = (match_specs(mesh, root.pattern, prog.cover),
                 {"count": P(), "store_groups": P(), "overflow": P()})
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(_comp_spec(root.pattern, prog.cover, P(ax)),),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def make_wcoj_list_step(pattern: Pattern, plan: WcojPlan, mesh: Mesh,
                        caps: EngineCaps, level_caps: Sequence[int]):
    """Jitted SPMD step: stacked partitions → (listed CompTensors, diag)
    — the WCOJ executor's stage 1, the generic-join twin of
    :func:`make_list_step`.

    Every device runs the anchored generic join over its partition
    (:func:`~repro.dist.jax_engine.wcoj_list`; complete & disjoint by the
    same center-anchoring argument as Lemma 3.1 — the anchor is adjacent
    to every other pattern vertex, so each match is found exactly once,
    at its anchor's center). The plain rows are wrapped as
    trivially-compressed tensors (skeleton = every column, empty sets) so
    the store init/maintain machinery downstream is shared verbatim with
    the tree executor. The group cap is ``level_caps[-1]`` — the rows are
    distinct matches already bounded by the final AGM-style level cap, so
    the wrap itself can never overflow.
    """
    axes = tuple(mesh.axis_names)
    ax = _flat_axes(mesh)
    cover_all = tuple(sorted(int(v) for v in pattern.vertices))
    ccaps = dataclasses.replace(caps, group_cap=int(level_caps[-1]))

    def body(pt_st: PaddedPartition):
        pt = jax.tree.map(lambda x: x[0], pt_st)
        tbl, valid, o1 = je.wcoj_list(pt, plan, caps, level_caps)
        tc, _, o2 = je.compress_plain(tbl, valid, plan.cols, cover_all, ccaps)
        diag = {
            "overflow": lax.psum(o1 + o2, axes),
            "matches_lower_bound": lax.psum(jnp.sum(tc.valid.astype(_I32)), axes),
        }
        return jax.tree.map(lambda x: x[None], tc), diag

    out_specs = (_comp_spec(pattern, cover_all, P(ax)),
                 {"overflow": P(), "matches_lower_bound": P()})
    fn = jax.shard_map(body, mesh=mesh, in_specs=(partition_specs(mesh),),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def make_wcoj_init_store_step(pattern: Pattern, ord_, mesh: Mesh,
                              caps: EngineCaps, store: StoreCaps,
                              level_caps: Sequence[int]):
    """Jitted SPMD step: (WCOJ listing from :func:`make_wcoj_list_step`)
    → (:class:`MatchStore`, diag) — :func:`make_init_store_step` for the
    trivially-compressed layout.

    Identical redistribution logic, but the ownership hash runs over
    *every* column (the WCOJ storage cover is all pattern vertices) and
    the input's group dim is the listing's final level cap rather than
    the engine group cap.
    """
    axes = tuple(mesh.axis_names)
    ax = _flat_axes(mesh)
    m = _mesh_size(mesh)
    cover_all = tuple(sorted(int(v) for v in pattern.vertices))
    n_s = len(cover_all)

    def body(tc_st: CompTensors):
        tc = jax.tree.map(lambda x: x[0], tc_st)
        me = _my_index(mesh)
        g = _gather_groups(tc, axes)
        mine = g.valid & (_owner_of(g.skeleton, tuple(range(n_s)), m) == me)
        st, ovf = je.merge_groups(g.skeleton, mine, g.sets,
                                  store.group_cap, store.set_cap)
        cnt = je.count_matches_dev(st, cover_all, ord_)
        diag = {
            "count": lax.psum(cnt, axes),
            "store_groups": lax.psum(jnp.sum(st.valid.astype(_I32)), axes),
            "overflow": lax.psum(ovf, axes),
        }
        out = MatchStore(skeleton=st.skeleton, valid=st.valid, sets=st.sets)
        return jax.tree.map(lambda x: x[None], out), diag

    out_specs = (match_specs(mesh, pattern, cover_all),
                 {"count": P(), "store_groups": P(), "overflow": P()})
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(_comp_spec(pattern, cover_all, P(ax)),),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def _wcoj_seed_mask(pt2: PaddedPartition, add: jnp.ndarray, axes):
    """Per-device ``[v_cap]`` anchor-seed mask for the delta-dataflow
    WCOJ patch: both endpoints of every inserted edge ``(a, b)`` and
    their common d'-neighbors ``N_{d'}(a) ∩ N_{d'}(b)``.

    Soundness: a new match contains an inserted edge ``(a, b)``, and the
    WCOJ anchor is adjacent to every other match vertex — so the anchor
    is ``a``, ``b``, or a common d'-neighbor of both. The dedup cap is
    exact (one slot per input element), so the mask can never drop a
    candidate. Common neighbors, not the union ``N(a) ∪ N(b)``: an
    endpoint next to a hub would otherwise seed the hub, and a hub anchor
    re-lists most of the graph's matches every batch.
    """
    add = add.astype(_I32)
    t = add.shape[0]
    ok = (add[:, 0] >= 0) & (add[:, 1] >= 0)
    ends = jnp.concatenate([add[:, 0], add[:, 1]])
    rows = lax.psum(je.center_adj_contrib(
        pt2, ends, jnp.concatenate([ok, ok])), axes) - 1   # [2T, D] d' rows
    ra, rb = rows[:t], rows[t:]
    common = (ra >= 0) & jnp.any(ra[:, :, None] == rb[:, None, :], axis=2)
    cids = jnp.concatenate([jnp.where(ok, add[:, 0], PAD),
                            jnp.where(ok, add[:, 1], PAD),
                            jnp.where(common, ra, PAD).reshape(-1)])
    cand, _, _ = je.dedup_rows(cids[:, None], cids >= 0,
                               max(int(cids.shape[0]), 1))
    _, hit = je.lookup_sorted(cand[:, 0], pt2.vertices)
    return hit


def _delete_table(dele: jnp.ndarray) -> jnp.ndarray:
    """Normalize one replicated delete batch into the lex-sorted
    PAD-tailed ``(hi, lo)`` table :func:`~repro.dist.jax_engine.edge_probe`
    consumes.

    Factored out of the per-pattern maintain body so a fused
    multi-pattern step runs the dedup **once** and fans the table out to
    every pattern's Lemma-6.1 filter. The cap is exact (one slot per
    batch row), so nothing can drop.
    """
    dele = dele.astype(_I32)
    bad = (dele[:, 0] < 0) | (dele[:, 1] < 0)
    d_pairs = jnp.stack(
        [jnp.where(bad, PAD, jnp.minimum(dele[:, 0], dele[:, 1])),
         jnp.where(bad, PAD, jnp.maximum(dele[:, 0], dele[:, 1]))], axis=1)
    d_tbl, _, _ = je.dedup_rows(d_pairs, d_pairs[:, 0] >= 0,
                                max(d_pairs.shape[0], 1))
    return d_tbl


def _maintain_local(st: MatchStore, patch: CompTensors, d_tbl: jnp.ndarray,
                    prog: TreeProgram, store: StoreCaps, skel_pairs,
                    comp_pairs, skel_cols, caps: EngineCaps):
    """One device's filter ∘ merge ∘ count over a precomputed patch and
    delete table — the pattern-specific tail shared by
    :func:`make_maintain_step` and :func:`make_maintain_mega_step`."""
    kept, removed = je.filter_deleted_dev(
        st.as_comp(), skel_pairs, comp_pairs, d_tbl[:, 0], d_tbl[:, 1],
        store.set_cap, use_pallas=caps.use_pallas)
    merged, movf = je.merge_tables_dev(kept, patch,
                                       store.group_cap, store.set_cap)
    cnt = je.count_matches_dev(merged, skel_cols, prog.ord)
    return merged, removed, movf, cnt


def make_maintain_step(prog: TreeProgram, units: Sequence[R1Unit], mesh: Mesh,
                       caps: EngineCaps, store: StoreCaps,
                       unit_caps: Optional[StoreCaps] = None):
    """Jitted SPMD step: (Φ(d'), store, E_a, E_d) → (store', patch, diag).

    The fused per-pattern result-maintenance half of a batch update —
    ``patch ∘ filter ∘ merge ∘ count`` in one compiled step, the device
    twin of :func:`repro.core.incremental.apply_update_to_matches`:

    1. Nav-join **patch** chains over the already-updated partitions
       (:func:`_patch_body`, Lemma 6.2 + Thm. 6.1), merged onto their
       full-skeleton owners;
    2. **filter** the local store shard against ``E_d``
       (:func:`~repro.dist.jax_engine.filter_deleted_dev`, Lemma 6.1 —
       probes through the Pallas kernel behind ``caps.use_pallas``);
    3. **merge** the surviving shard with the local patch shard
       (:func:`~repro.dist.jax_engine.merge_tables_dev`) — both sides
       obey the same ownership hash, so no collective is needed;
    4. **count** on device and ``psum`` (the only thing a count-only
       caller ever pulls to host is this scalar).

    The raw patch tensors are returned too so match-delta sinks can
    materialize exactly the new rows on demand; callers that don't pull
    them pay nothing. ``diag``: ``count``, ``patch_groups``,
    ``removed_groups``, ``store_groups``, ``overflow``, plus
    ``store_overflow`` (the :class:`StoreCaps` share of ``overflow`` —
    what a store auto-resize can actually fix, so resize logic gates on
    it, not on the summed counter).

    With ``unit_caps`` the step additionally threads the persistent
    unit-table carry of this pattern: signature becomes ``(pt2, store,
    carry, dirty, add, dele) → (store', patch, carry', diag)``. The
    chain-step and seed unit tables come from the carry; only devices
    whose ``dirty`` flag (the storage step's ``part_dirty``) is set
    re-run ``unit_list`` — behind a ``lax.cond``, so a clean partition
    pays zero listing work. ``diag`` gains ``unit_refreshes``.
    """
    axes = tuple(mesh.axis_names)
    ax = _flat_axes(mesh)
    pattern = prog.nodes[prog.root].pattern
    skel_cols = prog.nodes[prog.root].skel_cols
    chains = _chain_plans(units, pattern, prog.cover, prog.ord)
    skel_pairs, comp_pairs = je.deleted_edge_cols(pattern, skel_cols)
    if unit_caps is not None:
        plans, names = unit_plan_registry(prog, units)
        carry_specs = unit_carry_specs(prog, units, mesh)

    def maintain(pt2, st, patch, dele):
        """filter ∘ merge ∘ count over the already-computed local patch."""
        d_tbl = _delete_table(dele)
        return _maintain_local(st, patch, d_tbl, prog, store,
                               skel_pairs, comp_pairs, skel_cols, caps)

    if unit_caps is None:
        def body(pt2_st: PaddedPartition, st_st: MatchStore,
                 add: jnp.ndarray, dele: jnp.ndarray):
            pt2 = jax.tree.map(lambda x: x[0], pt2_st)
            st = jax.tree.map(lambda x: x[0], st_st)
            patch, povf = _patch_body(pt2, add, prog, chains, mesh, caps)
            merged, removed, movf, cnt = maintain(pt2, st, patch, dele)
            diag = {
                "count": lax.psum(cnt, axes),
                "patch_groups": lax.psum(jnp.sum(patch.valid.astype(_I32)), axes),
                "removed_groups": lax.psum(removed, axes),
                "store_groups": lax.psum(jnp.sum(merged.valid.astype(_I32)), axes),
                "overflow": lax.psum(povf + movf, axes),
                "store_overflow": lax.psum(movf, axes),
            }
            out = MatchStore(skeleton=merged.skeleton, valid=merged.valid,
                             sets=merged.sets)
            return (jax.tree.map(lambda x: x[None], out),
                    jax.tree.map(lambda x: x[None], patch), diag)

        diag_specs = {"count": P(), "patch_groups": P(), "removed_groups": P(),
                      "store_groups": P(), "overflow": P(),
                      "store_overflow": P()}
        out_specs = (match_specs(mesh, pattern, prog.cover),
                     _comp_spec(pattern, prog.cover, P(ax)), diag_specs)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(partition_specs(mesh),
                                     match_specs(mesh, pattern, prog.cover),
                                     P(), P()),
                           out_specs=out_specs, check_vma=False)
        return jax.jit(fn)

    def body_carry(pt2_st: PaddedPartition, st_st: MatchStore, carry_st,
                   dirty_st, add: jnp.ndarray, dele: jnp.ndarray):
        pt2 = jax.tree.map(lambda x: x[0], pt2_st)
        st = jax.tree.map(lambda x: x[0], st_st)
        carry = jax.tree.map(lambda x: x[0], carry_st)
        dirty = dirty_st[0]
        carry2, rovf = lax.cond(
            dirty,
            lambda: _refresh_units(pt2, plans, prog.cover, caps, unit_caps),
            lambda: (carry, jnp.int32(0)))
        by_key = {k: carry2[n] for k, n in names.items()}
        patch, povf = _patch_body(pt2, add, prog, chains, mesh, caps,
                                  unit_tables=by_key)
        merged, removed, movf, cnt = maintain(pt2, st, patch, dele)
        diag = {
            "count": lax.psum(cnt, axes),
            "patch_groups": lax.psum(jnp.sum(patch.valid.astype(_I32)), axes),
            "removed_groups": lax.psum(removed, axes),
            "store_groups": lax.psum(jnp.sum(merged.valid.astype(_I32)), axes),
            "overflow": lax.psum(povf + movf + rovf, axes),
            "store_overflow": lax.psum(movf, axes),
            "unit_refreshes": lax.psum(dirty.astype(_I32), axes),
        }
        out = MatchStore(skeleton=merged.skeleton, valid=merged.valid,
                         sets=merged.sets)
        return (jax.tree.map(lambda x: x[None], out),
                jax.tree.map(lambda x: x[None], patch),
                jax.tree.map(lambda x: x[None], carry2), diag)

    diag_specs = {"count": P(), "patch_groups": P(), "removed_groups": P(),
                  "store_groups": P(), "overflow": P(), "store_overflow": P(),
                  "unit_refreshes": P()}
    out_specs = (match_specs(mesh, pattern, prog.cover),
                 _comp_spec(pattern, prog.cover, P(ax)), carry_specs,
                 diag_specs)
    fn = jax.shard_map(body_carry, mesh=mesh,
                       in_specs=(partition_specs(mesh),
                                 match_specs(mesh, pattern, prog.cover),
                                 carry_specs, P(ax), P(), P()),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def donate_jit(fn, donate_argnums):
    """``jax.jit(fn, donate_argnums=...)`` on platforms that honor donation.

    On TPU the donated buffers are reused for the outputs, so a
    store-sized step updates in place instead of doubling resident
    memory. XLA:CPU ignores donation and warns on every call, so there
    the step is a plain ``jax.jit``. Either way callers treat the donated
    arguments as consumed: a retry rebuilds them from non-donated state,
    and the CPU tests exercise exactly those recovery paths.
    """
    if jax.default_backend() == "cpu":
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=tuple(donate_argnums))


@dataclasses.dataclass(frozen=True)
class MaintainSpec:
    """One pattern's slot in the fused multi-pattern maintain step.

    ``name`` keys this pattern's entries in the megastep's dict-valued
    inputs/outputs; ``prog``/``units`` are its compiled join-tree
    program, ``store`` its :class:`MatchStore` caps and ``unit_caps``
    the caps of its persistent unit-table carry.

    With ``wcoj`` set the slot runs the generic-join executor instead:
    the per-batch patch is a delta-seeded :func:`~repro.dist.jax_engine.wcoj_list`
    over Φ(d') (anchor seeds restricted to the inserted edges' endpoints
    and common neighbors, :func:`_wcoj_seed_mask`; matches
    filtered to those containing an inserted edge) with per-level caps
    ``wcoj_level_caps``, and the store holds trivially-compressed rows
    (storage cover = all pattern vertices, empty sets). Such a slot
    carries no unit tables — its carry entry is an empty pytree and its
    ``unit_refreshes`` diag is always 0.
    """

    name: str
    prog: TreeProgram
    units: Tuple[R1Unit, ...]
    store: StoreCaps
    unit_caps: StoreCaps
    wcoj: Optional[WcojPlan] = None
    wcoj_level_caps: Optional[Tuple[int, ...]] = None


def make_maintain_mega_step(specs: Sequence[MaintainSpec], mesh: Mesh,
                            caps: EngineCaps, donate: bool = True):
    """One jitted SPMD step maintaining *every* registered pattern.

    Signature: ``(Φ(d'), {name: store}, {name: carry}, dirty, E_a, E_d)
    → ({name: store'}, {name: patch}, {name: carry'}, {name: diag})``.

    Semantically identical to running each pattern's carry-threaded
    :func:`make_maintain_step` back to back — every per-pattern output
    is byte-identical — but fused into a single compiled program so a
    P-pattern service pays one dispatch, one delete-table dedup
    (:func:`_delete_table`), and one shared view of the updated
    partitions per batch instead of P. XLA additionally overlaps the
    independent per-pattern pipelines inside the one program.

    Per-pattern ``diag`` carries the same keys as the single-pattern
    step (``count``/``patch_groups``/``removed_groups``/``store_groups``
    /``overflow``/``store_overflow``/``unit_refreshes``), so callers
    can attribute cost and gate per-pattern auto-resize unchanged.

    With ``donate=True`` the store and carry dicts (argnums 1 and 2) are
    donated on platforms where XLA honors donation — they are the two
    store-shaped resident buffers, so donation keeps per-batch memory
    flat instead of 2× while the step runs. Callers must then treat the
    passed-in stores/carries as **consumed**: any retry after a failed
    batch (e.g. a strict-overflow abort) has to rebuild them from
    non-donated state (the partitions) rather than re-using the inputs.
    On CPU :func:`donate_jit` skips donation, but the contract is
    exercised there too.

    Each phase runs under a ``jax.named_scope`` — ``maintain/delete_table``,
    ``maintain/seed_mask`` and per pattern ``maintain/<name>/refresh``
    (tree slots), ``/patch`` and ``/store`` — so a profiler trace splits
    the fused step's device time by phase and pattern.
    """
    axes = tuple(mesh.axis_names)
    ax = _flat_axes(mesh)

    m = _mesh_size(mesh)
    pre = []
    for sp in specs:
        prog = sp.prog
        root = prog.nodes[prog.root]
        if sp.wcoj is not None:
            cover_all = tuple(sorted(int(v) for v in root.pattern.vertices))
            skel_pairs, comp_pairs = je.deleted_edge_cols(root.pattern,
                                                          cover_all)
            pre.append((sp, root.pattern, cover_all, None, skel_pairs,
                        comp_pairs, None, None))
            continue
        chains = _chain_plans(sp.units, root.pattern, prog.cover, prog.ord)
        skel_pairs, comp_pairs = je.deleted_edge_cols(root.pattern,
                                                      root.skel_cols)
        plans, names = unit_plan_registry(prog, sp.units)
        pre.append((sp, root.pattern, root.skel_cols, chains, skel_pairs,
                    comp_pairs, plans, names))
    any_wcoj = any(sp.wcoj is not None for sp in specs)

    def body(pt2_st: PaddedPartition, stores_st, carries_st, dirty_st,
             add: jnp.ndarray, dele: jnp.ndarray):
        pt2 = jax.tree.map(lambda x: x[0], pt2_st)
        dirty = dirty_st[0]
        with jax.named_scope("maintain/delete_table"):
            d_tbl = _delete_table(dele)  # shared across patterns
        # One delta-candidate anchor mask shared by every WCOJ slot —
        # pattern-independent (inserted endpoints + common neighbors).
        seed_mask = None
        if any_wcoj:
            with jax.named_scope("maintain/seed_mask"):
                seed_mask = _wcoj_seed_mask(pt2, add, axes)
        stores2, patches, carries2, diag = {}, {}, {}, {}
        for (sp, pattern, skel_cols, chains, skel_pairs, comp_pairs,
             plans, names) in pre:
            st = jax.tree.map(lambda x: x[0], stores_st[sp.name])
            carry = jax.tree.map(lambda x: x[0], carries_st[sp.name])
            scope = f"maintain/{sp.name}"
            if sp.wcoj is not None:
                # Delta-dataflow generic join: list — over the already
                # updated Φ(d') — exactly the matches that contain an
                # inserted edge and whose anchor is a delta candidate.
                # One pass over the full pattern, so no Thm. 6.1 dedup
                # is needed (a match with several inserted edges is
                # still listed once).
                carry2, rovf = carry, jnp.int32(0)
                me = _my_index(mesh)
                ccaps = dataclasses.replace(
                    caps, group_cap=int(sp.wcoj_level_caps[-1]))
                with jax.named_scope(f"{scope}/patch"):
                    tbl, valid, o1 = je.wcoj_list(
                        pt2, sp.wcoj, caps, sp.wcoj_level_caps,
                        require_edges=add.astype(_I32), seed_mask=seed_mask)
                    tc, _, o2 = je.compress_plain(tbl, valid, sp.wcoj.cols,
                                                  skel_cols, ccaps)
                    g = _gather_groups(tc, axes)
                    mine = g.valid & (_owner_of(g.skeleton,
                                                tuple(range(len(skel_cols))),
                                                m) == me)
                    # Store caps bound the merged shard, hence also this
                    # patch shard (patch ⊆ merged) — govf is store-sized.
                    patch, govf = je.merge_groups(g.skeleton, mine, g.sets,
                                                  sp.store.group_cap,
                                                  sp.store.set_cap)
                povf, sovf = o1 + o2, govf
            else:
                with jax.named_scope(f"{scope}/refresh"):
                    carry2, rovf = lax.cond(
                        dirty,
                        lambda pl=plans, cv=sp.prog.cover, uc=sp.unit_caps:
                            _refresh_units(pt2, pl, cv, caps, uc),
                        lambda c=carry: (c, jnp.int32(0)))
                by_key = {k: carry2[n] for k, n in names.items()}
                with jax.named_scope(f"{scope}/patch"):
                    patch, povf = _patch_body(pt2, add, sp.prog, chains,
                                              mesh, caps, unit_tables=by_key)
                sovf = jnp.int32(0)
            with jax.named_scope(f"{scope}/store"):
                merged, removed, movf, cnt = _maintain_local(
                    st, patch, d_tbl, sp.prog, sp.store, skel_pairs,
                    comp_pairs, skel_cols, caps)
            out = MatchStore(skeleton=merged.skeleton, valid=merged.valid,
                             sets=merged.sets)
            stores2[sp.name] = jax.tree.map(lambda x: x[None], out)
            patches[sp.name] = jax.tree.map(lambda x: x[None], patch)
            carries2[sp.name] = jax.tree.map(lambda x: x[None], carry2)
            refreshed = (jnp.int32(0) if sp.wcoj is not None
                         else dirty.astype(_I32))
            diag[sp.name] = {
                "count": lax.psum(cnt, axes),
                "patch_groups": lax.psum(jnp.sum(patch.valid.astype(_I32)),
                                         axes),
                "removed_groups": lax.psum(removed, axes),
                "store_groups": lax.psum(jnp.sum(merged.valid.astype(_I32)),
                                         axes),
                "overflow": lax.psum(povf + sovf + movf + rovf, axes),
                "store_overflow": lax.psum(sovf + movf, axes),
                "unit_refreshes": lax.psum(refreshed, axes),
            }
        return stores2, patches, carries2, diag

    per_diag = {"count": P(), "patch_groups": P(), "removed_groups": P(),
                "store_groups": P(), "overflow": P(), "store_overflow": P(),
                "unit_refreshes": P()}
    store_specs, patch_specs, carry_specs, diag_specs = {}, {}, {}, {}
    for (sp, pattern, skel_cols, *_rest) in pre:
        if sp.wcoj is not None:
            store_specs[sp.name] = match_specs(mesh, pattern, skel_cols)
            patch_specs[sp.name] = _comp_spec(pattern, skel_cols, P(ax))
            carry_specs[sp.name] = {}
        else:
            store_specs[sp.name] = match_specs(mesh, pattern, sp.prog.cover)
            patch_specs[sp.name] = _comp_spec(pattern, sp.prog.cover, P(ax))
            carry_specs[sp.name] = unit_carry_specs(sp.prog, sp.units, mesh)
        diag_specs[sp.name] = dict(per_diag)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(partition_specs(mesh), store_specs,
                                 carry_specs, P(ax), P(), P()),
                       out_specs=(store_specs, patch_specs, carry_specs,
                                  diag_specs),
                       check_vma=False)
    if donate:
        return donate_jit(fn, (1, 2))
    return jax.jit(fn)
