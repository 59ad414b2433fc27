"""Static-shape JAX executor of the shared listing/join plan IR.

This is the device half of the plan/executor split: the *plans*
(:class:`repro.core.plan.UnitPlan` / :class:`repro.core.plan.JoinPlan`)
are compiled once from pattern structure and executed either by the
NumPy host engine (:mod:`repro.core.match_engine`, ragged arrays) or by
this module on padded, statically-shaped tensors that jit/shard_map
cleanly onto a device mesh.

Design rules:

- Every array has a compile-time shape drawn from :class:`EngineCaps`;
  invalid slots hold :data:`PAD` (= -1) and carry explicit validity
  masks.
- Capacity can be exceeded at runtime (a partition listing more matches
  than ``match_cap``, a join producing more groups than ``group_cap``).
  Overflow is **never silent**: every compaction returns a dropped-row
  counter and all public entry points surface the sum. A zero counter is
  a proof that the padded result is exact.
- Results are bit-compatible with the host engine: converting a
  :class:`CompTensors` back with :func:`comp_to_host` and decompressing
  yields the identical match set (tested pattern-by-pattern).

``EngineCaps`` sizing: use the §IV-D match-size estimator
(``repro.core.estimator.match_size_estimate``) for ``match_cap`` /
``group_cap`` and degree statistics for ``deg_cap`` — see
``configs/ddsl_paper.py`` for the paper-scale example.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.graph import decode_edges
from repro.core.pattern import Pattern
from repro.core.plan import (
    LT, NEQ, JoinPlan, UnitPlan, WcojPlan, build_unit_plan, build_wcoj_plan,
)
from repro.core.storage import Partition
from repro.core.vcbc import CompressedTable, Ragged

__all__ = [
    "PAD",
    "EngineCaps",
    "PaddedPartition",
    "pad_partition",
    "build_unit_plan",
    "build_wcoj_plan",
    "UnitPlan",
    "WcojPlan",
    "JoinPlan",
    "unit_list",
    "wcoj_list",
    "require_edges_mask",
    "compress_plain",
    "group_rows",
    "scatter_grouped_values",
    "CompTensors",
    "comp_to_host",
    "ccjoin_local",
    "dedup_rows",
    "lookup_sorted",
    "edge_probe",
    "center_adj_contrib",
    "apply_edge_delta_rows",
    "patch_partition",
    "deleted_edge_cols",
    "filter_deleted_dev",
    "merge_groups",
    "merge_tables_dev",
    "count_matches_dev",
]

PAD = -1
_BIG = np.int32(np.iinfo(np.int32).max)
_I32 = jnp.int32
# Group-axis chunk of the k ≥ 4 count contraction (bounds the
# O(chunk·S^(k-1)) einsum intermediate; the group counts are independent
# so any chunk size is exact).
_COUNT_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class EngineCaps:
    """Static shape model of the device engine.

    v_cap      max vertices per partition
    deg_cap    max adjacency-row length
    e_cap      max edges per partition
    match_cap  max rows of a plain (uncompressed) match table
    group_cap  max skeleton groups of a compressed table
    set_cap    max values per compressed-vertex set
    pair_cap   max side-2 partners per side-1 group in a CC-join
    use_pallas route the engine's membership probes through the Pallas
               kernels (``repro.kernels``): compressed-set intersection
               in :func:`ccjoin_local` and edge-existence probes in
               :func:`unit_list` / :func:`wcoj_list` / :func:`edge_probe`.
               Results are bit-identical either way. ``None`` (the
               default) resolves to
               :func:`repro.kernels.autotune.default_use_pallas`: on for
               TPU (compiled kernels), off on the CPU backend, where the
               kernels only exist in interpret mode.
    """

    v_cap: int
    deg_cap: int
    e_cap: int
    match_cap: int
    group_cap: int
    set_cap: int
    pair_cap: int
    use_pallas: Optional[bool] = None

    def __post_init__(self):
        if self.use_pallas is None:
            from repro.kernels.autotune import default_use_pallas

            # frozen dataclass: resolve the platform default in place so
            # downstream tracing only ever sees a concrete bool.
            object.__setattr__(self, "use_pallas", default_use_pallas())


def _register(cls, fields):
    jax.tree_util.register_pytree_node(
        cls,
        lambda x: (tuple(getattr(x, f) for f in fields), None),
        lambda _, ch: cls(**dict(zip(fields, ch))),
    )
    return cls


@dataclasses.dataclass
class PaddedPartition:
    """One NP partition as padded tensors (all ``int32``/``bool``).

    ``vertices`` is ascending with ``PAD`` tail; ``adj`` rows are
    ascending global neighbor ids with ``PAD`` tail. ``edge_hi``/
    ``edge_lo`` are named for the *word of the host's int64 edge code*
    they carry (``code = min << 32 | max``): ``edge_hi`` is the high
    word = **min** endpoint, ``edge_lo`` the low word = **max**
    endpoint — this word-order naming is part of the external contract
    (tests read ``edge_hi`` as the smaller id). Rows are in
    lexicographic (code) order with ``PAD`` tails, the padded analogue
    of the host's sorted edge-code array.
    """

    vertices: jnp.ndarray   # [v_cap]
    center: jnp.ndarray     # [v_cap] bool
    deg: jnp.ndarray        # [v_cap]
    adj: jnp.ndarray        # [v_cap, deg_cap]
    edge_hi: jnp.ndarray    # [e_cap] (min endpoint)
    edge_lo: jnp.ndarray    # [e_cap] (max endpoint)


_register(PaddedPartition, ("vertices", "center", "deg", "adj", "edge_hi", "edge_lo"))


@dataclasses.dataclass
class CompTensors:
    """A VCBC compressed table as padded tensors.

    ``skeleton`` is ``[group_cap, n_skel_cols]`` (column labels travel
    out-of-band as the plan's ``skel_cols``), ``valid`` marks live
    groups, and ``sets`` maps each compressed vertex *label* to its
    ``[group_cap, set_cap]`` per-group value sets (``PAD`` tail, valid
    prefix ascending).
    """

    skeleton: jnp.ndarray
    valid: jnp.ndarray
    sets: Dict[int, jnp.ndarray]


_register(CompTensors, ("skeleton", "valid", "sets"))


# ---------------------------------------------------------------------------
# Padding host partitions
# ---------------------------------------------------------------------------

def pad_partition(part: Partition, caps: EngineCaps) -> PaddedPartition:
    """Pad one host :class:`Partition` to the static shape model.

    Storage caps (``v_cap``/``deg_cap``/``e_cap``) must hold the
    partition — shapes are compile-time, so a misfit here is a sizing
    error and raises instead of truncating.
    """
    nv = int(part.vertices.shape[0])
    ne = int(part.codes.shape[0])
    deg = np.diff(part.indptr).astype(np.int64)
    if nv > caps.v_cap:
        raise ValueError(f"partition has {nv} vertices > v_cap={caps.v_cap}")
    if ne > caps.e_cap:
        raise ValueError(f"partition has {ne} edges > e_cap={caps.e_cap}")
    if nv and int(deg.max(initial=0)) > caps.deg_cap:
        raise ValueError(f"max degree {int(deg.max())} > deg_cap={caps.deg_cap}")

    vertices = np.full(caps.v_cap, PAD, np.int32)
    center = np.zeros(caps.v_cap, bool)
    degs = np.zeros(caps.v_cap, np.int32)
    adj = np.full((caps.v_cap, caps.deg_cap), PAD, np.int32)
    vertices[:nv] = part.vertices
    center[:nv] = part.center_mask
    degs[:nv] = deg
    for r in range(nv):
        row = part.indices[part.indptr[r] : part.indptr[r + 1]]
        adj[r, : row.shape[0]] = row

    edge_hi = np.full(caps.e_cap, PAD, np.int32)
    edge_lo = np.full(caps.e_cap, PAD, np.int32)
    und = decode_edges(part.codes)  # sorted by code == lexicographic (lo, hi)
    edge_hi[:ne] = und[:, 0]
    edge_lo[:ne] = und[:, 1]
    return PaddedPartition(
        vertices=jnp.asarray(vertices), center=jnp.asarray(center),
        deg=jnp.asarray(degs), adj=jnp.asarray(adj),
        edge_hi=jnp.asarray(edge_hi), edge_lo=jnp.asarray(edge_lo),
    )


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def _row_of(pt: PaddedPartition, q: jnp.ndarray) -> jnp.ndarray:
    """Local row index of global vertex ids (callers mask misses)."""
    vs = jnp.where(pt.vertices < 0, _BIG, pt.vertices)
    r = jnp.searchsorted(vs, q.astype(_I32))
    return jnp.clip(r, 0, pt.vertices.shape[0] - 1)


def _lower_bound_pairs(qa: jnp.ndarray, qb: jnp.ndarray,
                       ea: jnp.ndarray, eb: jnp.ndarray) -> jnp.ndarray:
    """Insertion index of ``(qa, qb)`` pairs in a table sorted
    lexicographically ascending (``_BIG`` pads at the tail) — i.e. the
    count of table entries strictly below each query."""
    n = ea.shape[0]
    lo = jnp.zeros(qa.shape, _I32)
    hi = jnp.full(qa.shape, n, _I32)
    steps = max(1, int(math.ceil(math.log2(max(n, 2)))) + 1)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, n - 1)
        ma, mb = ea[midc], eb[midc]
        less = (ma < qa) | ((ma == qa) & (mb < qb))
        return jnp.where(less, mid + 1, lo), jnp.where(less, hi, mid)

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def _search_sorted_pairs(qa: jnp.ndarray, qb: jnp.ndarray,
                         ea: jnp.ndarray, eb: jnp.ndarray) -> jnp.ndarray:
    """Binary-search membership of ``(qa, qb)`` pairs in a table sorted
    lexicographically ascending (``_BIG`` pads at the tail)."""
    idx = jnp.clip(_lower_bound_pairs(qa, qb, ea, eb), 0, ea.shape[0] - 1)
    return (ea[idx] == qa) & (eb[idx] == qb)


def _probe_pallas(q_hi: jnp.ndarray, q_lo: jnp.ndarray,
                  t_hi: jnp.ndarray, t_lo: jnp.ndarray) -> jnp.ndarray:
    """The Pallas ``member_probe`` over queries of any shape.

    A query block whose last dim is not a whole number of 128-lane rows
    is PAD-extended to one before it is flattened: flattening
    ``[12352, 2512]`` directly made the TPU compile of that relayout
    take 75 s (described v5e), the padded form 5 s. Padded queries
    report False and are cut off again.
    """
    from repro.kernels.ops import member_probe

    shape = q_hi.shape
    if q_hi.ndim > 1 and shape[-1] % 128:
        pad = [(0, 0)] * (q_hi.ndim - 1) + [(0, -shape[-1] % 128)]
        hit = _probe_pallas(jnp.pad(q_hi, pad, constant_values=PAD),
                            jnp.pad(q_lo, pad, constant_values=PAD), t_hi, t_lo)
        return hit[..., :shape[-1]]
    return member_probe(q_hi.reshape(-1), q_lo.reshape(-1), t_hi, t_lo).reshape(shape)


def _has_edge(pt: PaddedPartition, u: jnp.ndarray, v: jnp.ndarray,
              use_pallas: bool = False) -> jnp.ndarray:
    """Vectorized edge membership: lexicographic binary search, or the
    Pallas tiled member-probe kernel when ``use_pallas`` is set."""
    qa = jnp.minimum(u, v).astype(_I32)
    qb = jnp.maximum(u, v).astype(_I32)
    if use_pallas:
        return _probe_pallas(qa, qb, pt.edge_hi, pt.edge_lo)
    ea = jnp.where(pt.edge_hi < 0, _BIG, pt.edge_hi)
    eb = jnp.where(pt.edge_lo < 0, _BIG, pt.edge_lo)
    return _search_sorted_pairs(qa, qb, ea, eb)


def _prefix_sum(x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Inclusive prefix sum along ``axis`` by log-step shifted adds
    (Hillis–Steele): the same integers as ``jnp.cumsum``. The form is
    for TPU compile time — a ``jnp.cumsum`` of 2^20 entries compiled in
    23 s for a described v5e, this in under a second at every length —
    and costs ``log2(n)`` elementwise passes at run time."""
    n = x.shape[axis]
    s = 1
    while s < n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (s, 0)
        x = x + jnp.pad(jax.lax.slice_in_dim(x, 0, n - s, axis=axis), pad)
        s *= 2
    return x


def _compact_index(ok: jnp.ndarray, cap: int):
    """Stable first-``cap`` packing of the ``ok`` entries.

    Returns ``(dest, valid, dropped)`` where ``dest`` maps each entry to
    its packed slot (``cap`` = dump slot for masked/overflowing entries)
    — the one compaction primitive behind every row/vector/group pack.
    """
    oki = ok.astype(_I32)
    idx = _prefix_sum(oki) - 1
    total = jnp.sum(oki)
    dest = jnp.where(ok & (idx < cap), idx, cap)
    valid = jnp.arange(cap) < jnp.minimum(total, cap)
    dropped = jnp.maximum(total - cap, 0)
    return dest, valid, dropped


def _take_index(ok: jnp.ndarray, cap: int):
    """Gather-side twin of :func:`_compact_index`: source indices of the
    first ``cap`` ``ok`` entries.

    Returns ``(src, valid, dropped)`` where ``src[p]`` is the index of
    the ``(p+1)``-th ``ok`` entry (clipped in range — mask with
    ``valid``). Packing via gather (cumsum + ``searchsorted`` on the
    nondecreasing prefix counts) instead of an N-slot scatter matters:
    XLA lowers scatters with serialized update semantics, so packing the
    ``match_cap·deg_cap`` frontier expansion through ``.at[dest].set``
    dominated the whole maintain step on CPU/GPU. The gather form is
    bit-identical (stable, first-``cap`` semantics).

    The gather is not free either: ``searchsorted`` is ``log2(n)``
    levels, each a per-element gather of ``cap`` queries, and XLA:TPU
    runs such gathers at ~86M elements a second on a v5e. One vector of
    ``cap`` queries is cheap; a row-wise repack of ``N`` rows is not —
    ``N = 32768, cap = 256`` costs ~97 ms a level — so
    :func:`_filter_set_rows` packs rows by a keys-only sort instead.
    """
    n = ok.shape[0]
    c = _prefix_sum(ok.astype(_I32))
    total = c[n - 1]
    src = jnp.searchsorted(c, jnp.arange(1, cap + 1, dtype=_I32))
    valid = jnp.arange(cap) < jnp.minimum(total, cap)
    dropped = jnp.maximum(total - cap, 0)
    return jnp.clip(src, 0, n - 1), valid, dropped


def _compact_rows(rows: jnp.ndarray, ok: jnp.ndarray, cap: int):
    """Keep the first ``cap`` ``ok`` rows; report the dropped count.

    rows: [N, C]; ok: [N] → ([cap, C] PAD-filled, [cap] valid, dropped).
    """
    if rows.shape[0] == 0:
        return (jnp.full((cap, rows.shape[1]), PAD, _I32),
                jnp.zeros((cap,), bool), jnp.int32(0))
    src, valid, dropped = _take_index(ok, cap)
    out = jnp.where(valid[:, None], rows[src].astype(_I32), PAD)
    return out, valid, dropped


def _compact_vec(vals: jnp.ndarray, ok: jnp.ndarray, cap: int, fill=0):
    """1-D variant of :func:`_compact_rows`."""
    if vals.shape[0] == 0:
        return (jnp.full((cap,), fill, vals.dtype),
                jnp.zeros((cap,), bool), jnp.int32(0))
    src, valid, dropped = _take_index(ok, cap)
    out = jnp.where(valid, vals[src], jnp.asarray(fill, vals.dtype))
    return out, valid, dropped


# Candidates (frontier row, pivot neighbor) that one extension step
# examines at once. A row proposes only its pivot's real neighbors (the
# adjacency row's length, not its ``deg_cap`` padding), so the work is
# O(Σ pivot degree) rather than O(rows · deg_cap); the candidates run as
# a sequential loop over fixed-size blocks in row-major order, each
# appending its survivors to the packed output, and the transient device
# memory is O(block).
_EXPAND_CHUNK = 1 << 20


def _extend(pt: PaddedPartition, tbl: jnp.ndarray, valid: jnp.ndarray,
            pivot: int, min_degree: int, edge_cols: Sequence[int],
            ord_checks, cap: int, use_pallas: bool):
    """Extend a frontier table by one pattern vertex.

    Each valid row proposes the pivot's neighbors; a candidate survives
    the degree prune, injectivity, an edge to every ``edge_cols`` column
    and the symmetry-breaking order. Returns ``(tbl [cap, k+1], valid,
    overflow)`` — the first ``cap`` survivors in row-major order.
    """
    W, k = tbl.shape
    D = pt.adj.shape[1]
    prow = _row_of(pt, tbl[:, pivot])
    n_nb = jnp.where(valid, pt.deg[prow], 0)      # adjacency rows: PAD tail
    ends = _prefix_sum(n_nb)
    n_cand = ends[W - 1]
    B = max(1, min(_EXPAND_CHUNK, W * D))
    bcap = min(B, cap)

    def block(i, carry):
        out, total = carry
        e = i * B + jnp.arange(B, dtype=_I32)      # candidate ordinals
        r = jnp.minimum(jnp.searchsorted(ends, e, side="right"), W - 1)
        t = tbl[r]                                            # [B, k]
        col = jnp.clip(e - (ends[r] - n_nb[r]), 0, D - 1)
        cand = pt.adj[prow[r], col]                           # [B]
        ok = (e < n_cand) & (cand >= 0)
        ok &= pt.deg[_row_of(pt, cand)] >= min_degree         # MC₁ degree prune
        for j in range(k):                                    # injectivity
            ok &= cand != t[:, j]
        for j in edge_cols:                                   # edges to placed cols
            ok &= _has_edge(pt, cand, t[:, j], use_pallas=use_pallas)
        for j, greater in ord_checks:                         # SimB order
            ok &= (cand > t[:, j]) if greater else (cand < t[:, j])
        part, _, _ = _compact_rows(
            jnp.concatenate([t, cand[:, None]], axis=1), ok, bcap)
        # PAD tail of `part` is overwritten by the next block's survivors;
        # writes past `cap` land in the dump rows and are cut below.
        out = jax.lax.dynamic_update_slice_in_dim(
            out, part, jnp.minimum(total, cap), 0)
        return out, total + jnp.sum(ok.astype(_I32))

    out, total = jax.lax.fori_loop(
        0, (n_cand + B - 1) // B, block,
        (jnp.full((cap + bcap, k + 1), PAD, _I32), jnp.int32(0)))
    out_valid = jnp.arange(cap) < jnp.minimum(total, cap)
    return (jnp.where(out_valid[:, None], out[:cap], PAD), out_valid,
            jnp.maximum(total - cap, 0))


# ---------------------------------------------------------------------------
# Unit listing (plan executor)
# ---------------------------------------------------------------------------

def unit_list(
    pt: PaddedPartition,
    plan: UnitPlan,
    caps: EngineCaps,
    require_edges: jnp.ndarray | None = None,
):
    """Anchored frontier-table listing of one R1 unit (``M_ac``).

    Returns ``(table [match_cap, |V|], valid [match_cap], overflow)``
    with table columns aligned to ``plan.cols`` (the extension order).
    ``require_edges`` (``[k, 2]`` int32) restricts to matches mapping at
    least one pattern edge into the given edge set (Nav-join seeds).
    """
    # --- seed the anchor column ---------------------------------------------
    seed_ok = pt.center & (pt.vertices >= 0) & (pt.deg >= plan.anchor_min_degree)
    tbl, valid, ovf = _compact_rows(pt.vertices[:, None], seed_ok, caps.match_cap)

    # --- extend vertex by vertex --------------------------------------------
    for step in plan.steps:
        tbl, valid, o = _extend(pt, tbl, valid, step.pivot, step.min_degree,
                                step.edge_checks, step.ord_checks,
                                caps.match_cap, caps.use_pallas)
        ovf = ovf + o

    # --- inserted-edge requirement (Nav-join step 2) ------------------------
    if require_edges is not None:
        valid = valid & require_edges_mask(tbl, plan.edge_cols, require_edges)
    return tbl, valid, ovf


def wcoj_list(
    pt: PaddedPartition,
    plan: WcojPlan,
    caps: EngineCaps,
    level_caps: Sequence[int],
    require_edges: jnp.ndarray | None = None,
    seed_mask: jnp.ndarray | None = None,
):
    """Anchored generic-join listing of a whole pattern (WCOJ executor).

    A padded, static-shape scan over extension levels (unrolled so each
    level owns its shape): every level gathers the pivot's adjacency,
    intersects it against the adjacency of the other placed neighbors
    (the same edge-membership probes as :func:`unit_list`, Pallas-routed
    behind ``caps.use_pallas``), and packs survivors to that level's
    candidate cap. ``level_caps`` has one entry per placed prefix length
    (``level_caps[0]`` caps the seed), sized from the §IV-D per-prefix
    estimates — so each level's table is bounded by *its own* (AGM-style)
    prefix estimate instead of :func:`unit_list`'s single uniform
    ``match_cap``. On cliques the prefix estimates shrink level over
    level, which is exactly where the generic join wins: the tree
    executor pays the max-frontier width on every step.

    Returns ``(table [level_caps[-1], |V|], valid, overflow)`` with
    columns aligned to ``plan.cols``; ``require_edges`` restricts to
    matches mapping ≥1 pattern edge into the given edge set (the
    delta-dataflow seed restriction for incremental maintenance), and
    ``seed_mask`` (``[v_cap]`` bool) further restricts the anchor seeds —
    the incremental path passes the delta-candidate vertex set here so a
    batch only re-explores the neighborhood the delta can touch.
    """
    k = len(plan.order)
    level_caps = tuple(int(c) for c in level_caps)
    if len(level_caps) != k:
        raise ValueError(f"need {k} level caps (incl. seed), got {len(level_caps)}")

    # --- seed the anchor column (level 0) -----------------------------------
    seed_ok = pt.center & (pt.vertices >= 0) & (pt.deg >= plan.anchor_min_degree)
    if seed_mask is not None:
        seed_ok = seed_ok & seed_mask
    tbl, valid, ovf = _compact_rows(pt.vertices[:, None], seed_ok, level_caps[0])

    # --- extend level by level (adjacency intersection = edge checks) -------
    for i, lv in enumerate(plan.levels, start=1):
        tbl, valid, o = _extend(pt, tbl, valid, lv.pivot, lv.min_degree,
                                lv.intersect_cols, lv.ord_checks,
                                level_caps[i], caps.use_pallas)
        ovf = ovf + o

    # --- inserted-edge requirement (delta-dataflow seeds) -------------------
    if require_edges is not None:
        valid = valid & require_edges_mask(tbl, plan.edge_cols, require_edges)
    return tbl, valid, ovf


def require_edges_mask(
    tbl: jnp.ndarray,
    edge_cols: Sequence[tuple],
    require_edges: jnp.ndarray,
) -> jnp.ndarray:
    """Rows of a plain match table mapping ≥1 pattern edge into a small
    replicated edge set (the Nav-join seed restriction, §VI-B step 2).

    Factored out of :func:`unit_list` so a cached full unit table can be
    re-seeded per batch with the same filter the listing itself would
    have applied — bit-identical either way.
    """
    ra = jnp.minimum(require_edges[:, 0], require_edges[:, 1]).astype(_I32)
    rb = jnp.maximum(require_edges[:, 0], require_edges[:, 1]).astype(_I32)
    hit = jnp.zeros(tbl.shape[0], bool)
    for ia, ib in edge_cols:
        lo = jnp.minimum(tbl[:, ia], tbl[:, ib])
        hi = jnp.maximum(tbl[:, ia], tbl[:, ib])
        hit |= jnp.any((lo[:, None] == ra[None, :]) & (hi[:, None] == rb[None, :]), axis=1)
    return hit


# ---------------------------------------------------------------------------
# Compression (plain table → CompTensors)
# ---------------------------------------------------------------------------

def _lex_order(keys: jnp.ndarray) -> jnp.ndarray:
    """Stable row order sorting ``keys [N, C]`` lexicographically (col 0
    primary).

    Least-significant-first passes, each a stable sort on one column:
    the same permutation as a C-key ``jnp.lexsort``. A pass sorts the
    unique pair (column, position) with no payload, which orders ties by
    position without a stable sort. The form matters for TPU compile
    time, which these sorts dominate in every service step: for a
    described v5e, a C-key sort compiles in 33 s (2 keys) to 79 s
    (4 keys), a stable key+payload sort in 28 s, this one in 16 s, and
    identical sorts in one program compile once.
    """
    pos = jnp.arange(keys.shape[0], dtype=_I32)
    perm = None
    for j in reversed(range(keys.shape[1])):
        col = keys[:, j] if perm is None else keys[perm, j]
        _, p = jax.lax.sort((col, pos), num_keys=2, is_stable=False)
        perm = p if perm is None else perm[p]
    return perm if perm is not None else pos


def group_rows(rows: jnp.ndarray, ok: jnp.ndarray, n_groups: int):
    """Assign group ids to the distinct valid rows of ``rows [N, S]``.

    Sorts lexicographically (invalid rows pushed past ``_BIG``), scatters
    one representative per distinct row, and returns
    ``(skeleton [n_groups, S], gvalid, order, g_eff, dropped_groups)``
    where ``order`` is the sort permutation and ``g_eff [N]`` maps each
    *sorted* row to its group (dump index ``n_groups`` for invalid or
    overflowing rows). Shared by plain-table compression and the
    cross-chain patch merge.
    """
    G, S = n_groups, rows.shape[1]
    keys = jnp.where(ok[:, None], rows, _BIG)
    if S:
        order = _lex_order(keys)
    else:
        order = jnp.argsort(~ok)
    ks = keys[order]
    vs_ = ok[order]
    if S:
        prev = jnp.concatenate([jnp.full((1, S), -2, _I32), ks[:-1]], axis=0)
        newg = jnp.any(ks != prev, axis=1) & vs_
    else:
        newg = jnp.concatenate([jnp.ones(1, bool), jnp.zeros(ks.shape[0] - 1, bool)]) & vs_
    gid = _prefix_sum(newg.astype(_I32)) - 1
    # Representatives = the first G group-leader rows, packed by gather
    # (see _take_index) — identical to the old per-leader scatter.
    skeleton, gvalid, dropped = _compact_rows(ks, newg, G)
    g_eff = jnp.where(vs_ & (gid < G), gid, G)
    return skeleton, gvalid, order, g_eff, dropped


def scatter_grouped_values(g: jnp.ndarray, vals: jnp.ndarray, n_groups: int,
                           set_cap: int):
    """Dedup a ``(group, value)`` stream and pack per-group sorted sets.

    ``g`` uses ``n_groups`` as the dump index for invalid entries.
    Returns ``([n_groups, set_cap]`` PAD-tailed ascending sets,
    dropped-unique-value count)`` — the one packing primitive behind
    both plain-table compression and cross-chain set merging.
    """
    n = g.shape[0]
    o2 = _lex_order(jnp.stack([g, vals], axis=1))
    g2, v2 = g[o2], vals[o2]
    pv = g2 < n_groups
    prevg = jnp.concatenate([jnp.full((1,), -2, _I32), g2[:-1]])
    prevv = jnp.concatenate([jnp.full((1,), -2, _I32), v2[:-1]])
    isnew = pv & ((g2 != prevg) | (v2 != prevv))
    cum = _prefix_sum(isnew.astype(_I32))           # uniques up to & incl. i
    # Gather pack (see _take_index): per-group bases come from each
    # group's first index in the (group, value)-sorted stream, and the
    # (s+1)-th unique value of group ``gi`` sits where ``cum`` first
    # reaches ``base[gi] + s + 1`` — no N-element scatter anywhere.
    start = jnp.searchsorted(g2, jnp.arange(n_groups + 1, dtype=_I32))
    cum0 = cum - isnew.astype(_I32)                 # uniques strictly before i
    base = jnp.where(start >= n, cum[-1], cum0[jnp.clip(start, 0, n - 1)])
    counts = base[1:] - base[:-1]                   # unique values per group
    dropped = jnp.sum(jnp.maximum(counts - set_cap, 0))
    tgt = base[:-1, None] + jnp.arange(1, set_cap + 1, dtype=_I32)[None, :]
    idx = jnp.searchsorted(cum, tgt.reshape(-1)).reshape(n_groups, set_cap)
    ok = jnp.arange(set_cap)[None, :] < jnp.minimum(counts, set_cap)[:, None]
    out = jnp.where(ok, v2[jnp.clip(idx, 0, n - 1)], PAD)
    return out, dropped


def compress_plain(
    tbl: jnp.ndarray,
    valid: jnp.ndarray,
    cols: Sequence[int],
    cover: Sequence[int],
    caps: EngineCaps,
):
    """Group a plain match table by its skeleton columns (§IV-A).

    Returns ``(CompTensors, skel_cols, overflow)``; ``skel_cols`` is the
    sorted tuple of cover labels present in ``cols``.
    """
    cols = tuple(int(c) for c in cols)
    cover_set = {int(c) for c in cover}
    skel_labels = tuple(c for c in sorted(cols) if c in cover_set)
    comp_labels = tuple(c for c in sorted(cols) if c not in cover_set)
    skel_idx = [cols.index(c) for c in skel_labels]
    G, S = caps.group_cap, len(skel_labels)

    skel = tbl[:, skel_idx] if S else tbl[:, :0]
    skeleton, gvalid, order, g_eff, ovf = group_rows(skel, valid, G)

    sets: Dict[int, jnp.ndarray] = {}
    for c in comp_labels:
        vals = tbl[:, cols.index(c)][order]
        sets[c], dropped = scatter_grouped_values(g_eff, vals, G, caps.set_cap)
        ovf = ovf + dropped
    return CompTensors(skeleton=skeleton, valid=gvalid, sets=sets), skel_labels, ovf


def comp_to_host(
    tc: CompTensors,
    pattern: Pattern,
    cover: Sequence[int],
    skel_cols: Sequence[int],
) -> CompressedTable:
    """Convert padded VCBC tensors back into a host :class:`CompressedTable`."""
    skel = np.asarray(tc.skeleton, np.int64)
    valid = np.asarray(tc.valid, bool)
    keep = np.nonzero(valid)[0]
    rows = skel[keep]
    comp: Dict[int, Ragged] = {}
    for v in sorted(int(k) for k in tc.sets):
        a = np.asarray(tc.sets[v], np.int64)[keep]
        g, s = np.nonzero(a >= 0)
        comp[int(v)] = Ragged.from_group_ids(
            g.astype(np.int64), a[g, s], rows.shape[0]
        )
    return CompressedTable(
        pattern=pattern,
        cover=tuple(sorted(int(c) for c in cover)),
        skeleton_cols=tuple(int(c) for c in skel_cols),
        skeleton=rows,
        comp=comp,
    )


# ---------------------------------------------------------------------------
# Local CC-join (plan executor)
# ---------------------------------------------------------------------------

def _filter_set_rows(vals: jnp.ndarray, ok: jnp.ndarray, set_cap: int):
    """Re-pack each row's surviving values into a valid prefix.

    ``vals [N, C]``, ``ok [N, C]`` → ``([N, set_cap] PAD-tailed, [N]
    counts)``: row ``i`` keeps its first ``set_cap`` ``ok`` values in
    order, and ``counts[i]`` is the number of ``ok`` values in it, all of
    them (so ``counts - set_cap`` is what a row wider than ``set_cap``
    drops).

    Precondition: the ``ok`` values of each row are nonnegative and
    ascending in row order — every caller repacks canonical sets
    (ascending, PAD-tailed) or, in :func:`merge_tables_dev`, a row sorted
    just before. Packing them into a prefix is then a row sort with the
    dropped entries pushed past ``_BIG``: a keys-only sort, no gather.

    A row repack must not gather element-wise on TPU. Finding each
    output slot's source by a vmapped ``jnp.searchsorted`` over the
    row's prefix counts is a scan of ``ceil(log2(C + 1))`` levels, each
    a per-element gather of ``N·set_cap`` queries, and XLA:TPU runs
    such gathers almost serially: on a v5e a level of ``N = 32768,
    set_cap = 256`` (8.4M queries) costs ~97 ms, ~86M elements a second,
    and the two repacks of a ``q2_triangle`` store sweep (9 and 10
    levels) cost ~1.85 s of a 4.3 s batch that way.
    """
    keys = jax.lax.sort(jnp.where(ok, vals.astype(_I32), _BIG), dimension=1,
                        is_stable=False)
    w = keys.shape[1]
    if w >= set_cap:
        keys = keys[:, :set_cap]
    else:
        keys = jnp.pad(keys, ((0, 0), (0, set_cap - w)), constant_values=_BIG)
    out = jnp.where(keys == _BIG, PAD, keys)
    return out, jnp.sum(ok.astype(_I32), axis=1)


def ccjoin_local(
    tA: CompTensors,
    tB: CompTensors,
    plan: JoinPlan,
    caps: EngineCaps,
):
    """Execute one CC-join plan on co-located compressed tensors.

    Returns ``(CompTensors, overflow)``. Overflow counts both pair slots
    beyond ``pair_cap`` and output groups beyond ``group_cap``.
    """
    GA, GB = tA.skeleton.shape[0], tB.skeleton.shape[0]
    eq = tA.valid[:, None] & tB.valid[None, :]
    for ka, kb in zip(plan.key_left_idx, plan.key_right_idx):
        eq &= tA.skeleton[:, ka][:, None] == tB.skeleton[:, kb][None, :]

    # Pack each group's first pair_cap partners by row-wise gather
    # (cumsum + searchsorted): the old formulation scattered a GA×GB
    # index matrix into [GA, pair_cap+1] slots, which XLA serializes —
    # at engine caps that is a multi-10M-element scatter per join. The
    # gather keeps the identical ascending-gb pair order.
    cnt = _prefix_sum(eq.astype(_I32), axis=1)               # [GA, GB]
    row_tot = cnt[:, -1]
    ovf = jnp.sum(jnp.maximum(row_tot - caps.pair_cap, 0))
    tgt = jnp.arange(1, caps.pair_cap + 1, dtype=_I32)
    sel = jax.vmap(lambda row: jnp.searchsorted(row, tgt))(cnt)
    pslot = tgt[None, :] <= jnp.minimum(row_tot, caps.pair_cap)[:, None]
    pair_b = jnp.where(pslot, jnp.clip(sel, 0, GB - 1), -1).reshape(-1)
    pvalid = pair_b >= 0                                     # [GA * pair_cap]
    ga = jnp.repeat(jnp.arange(GA, dtype=_I32), caps.pair_cap)
    gb = jnp.clip(pair_b, 0, GB - 1)

    n_out = len(plan.skel_out)
    s3 = jnp.zeros((ga.shape[0], n_out), _I32)
    for out_j, left_j in plan.out_from_left:
        s3 = s3.at[:, out_j].set(tA.skeleton[ga, left_j])
    for out_j, right_j in plan.out_from_right:
        s3 = s3.at[:, out_j].set(tB.skeleton[gb, right_j])
    for ja, jb in plan.pair_neq:
        pvalid &= s3[:, ja] != s3[:, jb]
    for ja, jb in plan.pair_ord:
        pvalid &= s3[:, ja] < s3[:, jb]

    # Compact surviving pairs into group slots, then materialize sets.
    triple = jnp.concatenate([s3, ga[:, None], gb[:, None]], axis=1)
    packed, out_valid, o2 = _compact_rows(triple, pvalid, caps.group_cap)
    ovf = ovf + o2
    out_skel = packed[:, :n_out]
    ga_c = jnp.clip(packed[:, n_out], 0, GA - 1)
    gb_c = jnp.clip(packed[:, n_out + 1], 0, GB - 1)

    sets: Dict[int, jnp.ndarray] = {}
    for cp in plan.comp:
        v = cp.vertex
        if cp.source == "both":
            a = tA.sets[v][ga_c]
            b = tB.sets[v][gb_c]
            if caps.use_pallas:
                from repro.kernels.ops import set_intersect

                ok = set_intersect(a, b, pad=PAD)
            else:
                ok = (a >= 0) & jnp.any(a[:, :, None] == b[:, None, :], axis=2)
            vals = a
        elif cp.source == "left":
            vals = tA.sets[v][ga_c]
            ok = vals >= 0
        else:
            vals = tB.sets[v][gb_c]
            ok = vals >= 0
        for col, mode in cp.checks:
            sv = out_skel[:, col][:, None]
            if mode == NEQ:
                ok &= vals != sv
            elif mode == LT:
                ok &= vals < sv
            else:
                ok &= vals > sv
        packed_vals, counts = _filter_set_rows(vals, ok & out_valid[:, None], caps.set_cap)
        sets[v] = packed_vals
        out_valid = out_valid & (counts > 0)   # host drops empty-set groups

    return CompTensors(skeleton=out_skel, valid=out_valid, sets=sets), ovf


# ---------------------------------------------------------------------------
# Candidate-restricted update primitives (Alg. 4 C1–C3 on device)
# ---------------------------------------------------------------------------
#
# The delta-restricted storage update (``repro.dist.sharded``) works on
# *candidate* sets sized by the update batch, not the graph: candidate
# vertex ids, their gathered adjacency rows, and candidate edges whose
# NP membership must be re-evaluated. These are its static-shape
# building blocks; every compaction reports dropped entries.

def dedup_rows(rows: jnp.ndarray, ok: jnp.ndarray, cap: int):
    """Unique valid rows, lexicographically ascending, packed to ``cap``.

    Returns ``([cap, C] PAD-filled, [cap] valid, dropped_unique)`` — the
    candidate-set compaction (C1 endpoints, C1 ∪ N(C1) vertices,
    candidate edge pairs) with an explicit overflow counter.
    """
    if rows.shape[1] == 1:
        # Vertex ids: a keys-only sort, no permutation — for TPU it
        # compiles in a fraction of a permutation sort's time.
        ks = jax.lax.sort(jnp.where(ok, rows[:, 0].astype(_I32), _BIG),
                          is_stable=False)
        prev = jnp.concatenate([jnp.full((1,), -2, _I32), ks[:-1]])
        return _compact_rows(ks[:, None], (ks != prev) & (ks != _BIG), cap)
    skeleton, valid, _, _, dropped = group_rows(rows, ok, cap)
    return skeleton, valid, dropped


def lookup_sorted(table: jnp.ndarray, q: jnp.ndarray):
    """Position of ``q`` in an ascending PAD-tailed id table.

    Returns ``(idx, hit)``; ``idx`` is clipped so callers can gather
    unconditionally and mask with ``hit``.
    """
    t = jnp.where(table < 0, _BIG, table)
    idx = jnp.clip(jnp.searchsorted(t, q.astype(_I32)), 0, table.shape[0] - 1)
    hit = (table[idx] == q) & (q >= 0)
    return idx, hit


def edge_probe(
    q_hi: jnp.ndarray,
    q_lo: jnp.ndarray,
    t_hi: jnp.ndarray,
    t_lo: jnp.ndarray,
    use_pallas: bool = False,
):
    """Membership of ``(hi, lo)`` query pairs in a small edge table.

    The candidate probe path of the delta update: local stored edges are
    probed against the (candidate ∪ deleted) edge table. The table must
    be sorted lexicographically ascending with ``(-1, -1)`` pads at the
    tail (``dedup_rows`` output order); queries may pad anywhere. Routes
    through the Pallas ``member_probe`` kernel when ``use_pallas`` is
    set (a VPU tile sweep, order-insensitive); the binary-search
    fallback keeps the host path at ``O(Q log T)`` — both are
    bit-identical.
    """
    if use_pallas:
        return _probe_pallas(q_hi, q_lo, t_hi, t_lo)
    pad_t = (t_hi == -1) & (t_lo == -1)
    ea = jnp.where(pad_t, _BIG, t_hi.astype(_I32))
    eb = jnp.where(pad_t, _BIG, t_lo.astype(_I32))
    hit = _search_sorted_pairs(q_hi.astype(_I32), q_lo.astype(_I32), ea, eb)
    return hit & ~((q_hi == -1) & (q_lo == -1))


def center_adj_contrib(pt: PaddedPartition, ids: jnp.ndarray, ok: jnp.ndarray):
    """This partition's (+1-encoded) adjacency rows for candidate ids.

    Only the *center* copy of a vertex holds its full neighborhood, so
    exactly one device contributes a non-zero row per id; callers
    ``lax.psum`` the result across the mesh and subtract 1 (absent ids
    come back as all-PAD rows). This is the candidate gather that
    replaces the full-graph adjacency all-reduce.
    """
    row = _row_of(pt, ids)
    hit = ok & (ids >= 0) & (pt.vertices[row] == ids) & pt.center[row]
    return jnp.where(hit[:, None], pt.adj[row] + 1, 0).astype(_I32)


def apply_edge_delta_rows(
    ids: jnp.ndarray,
    rows: jnp.ndarray,
    add: jnp.ndarray,
    dele: jnp.ndarray,
    nv_limit: int,
    count_overflow: bool = True,
):
    """Apply one edge batch to the adjacency rows of ``ids``.

    ``rows`` is ``[K, D]`` PAD-tailed ascending; ``add``/``dele`` are
    ``[T, 2]`` with negative rows as padding. Deletes mask matching
    neighbors; adds insert idempotently into a free slot (rows with no
    free slot count toward the returned overflow). Endpoints ≥
    ``nv_limit`` are skipped like the full-gather oracle. Result rows
    are re-sorted ascending with PAD tails.
    """
    K, D = rows.shape
    r = jnp.where(rows < 0, _BIG, rows.astype(_I32))
    rowidx = jnp.arange(K)

    # Batch rows apply in order (a rolled loop: one copy of the body in
    # the program instead of one per batch row — the TPU compile time).
    def delete(t, r):
        a, b = dele[t, 0], dele[t, 1]
        for u, w in ((a, b), (b, a)):
            sel = (ids == u) & (u >= 0)
            r = jnp.where(sel[:, None] & (r == w), _BIG, r)
        return r

    def insert(t, carry):
        r, ovf = carry
        a, b = add[t, 0], add[t, 1]
        bad = (a < 0) | (b < 0) | (a >= nv_limit) | (b >= nv_limit)
        for u, w in ((a, b), (b, a)):
            sel = (ids == u) & ~bad
            present = jnp.any(r == w, axis=1)
            free = r == _BIG
            has = jnp.any(free, axis=1)
            slot = jnp.argmax(free, axis=1)
            ins = sel & has & ~present
            if count_overflow:
                ovf = ovf + jnp.sum((sel & ~has & ~present).astype(_I32))
            r_ext = jnp.concatenate([r, jnp.full((K, 1), _BIG, _I32)], axis=1)
            r = r_ext.at[rowidx, jnp.where(ins, slot, D)].set(w)[:, :D]
        return r, ovf

    r = jax.lax.fori_loop(0, dele.shape[0], delete, r)
    r, ovf = jax.lax.fori_loop(0, add.shape[0], insert, (r, jnp.int32(0)))
    r = jnp.sort(r, axis=1)
    return jnp.where(r == _BIG, PAD, r), ovf


def patch_partition(
    pt: PaddedPartition,
    cand: jnp.ndarray,
    cand_valid: jnp.ndarray,
    drop_hi: jnp.ndarray,
    drop_lo: jnp.ndarray,
    ins_hi: jnp.ndarray,
    ins_lo: jnp.ndarray,
    ins_ok: jnp.ndarray,
    nv_glob: int,
    m: int,
    me: jnp.ndarray,
    caps: EngineCaps,
    use_pallas: bool = False,
):
    """Patch a stored partition in place: drop then insert edge sets.

    ``cand`` is the ascending PAD-tailed candidate vertex table — every
    dropped or inserted edge has **both endpoints in it** (the C2
    closure), so only candidate rows can change and everything else is
    a pure gather. ``(drop_hi, drop_lo)`` is a lex-sorted PAD-tailed
    edge table (the :func:`edge_probe` contract); ``(ins_hi, ins_lo,
    ins_ok)`` are (min, max) pairs to insert, already deduped and
    disjoint from the surviving stored edges (the delta update
    guarantees this: every insertion is a candidate edge, and all
    candidate edges are dropped).

    The point of this shape: no index-carrying sort (XLA's
    scalar-comparator argsort/lexsort path) ever touches a graph-sized
    array. Candidate rows are drop-probed, merged with their insertions
    by a candidate-sized row sort, and scattered into the remapped
    layout; the only |V|-sized work is gathers, scatters, and cumsum
    compactions (bandwidth-bound, same order as writing the output at
    all). Produces the oracle's canonical layout (ascending PAD-tailed
    vertices and adjacency rows, lexicographic edge list); returns
    ``(partition, overflow)``.
    """
    D = caps.deg_cap
    K = cand.shape[0]
    # 1. candidate rows in the old layout, drop-probed (delta-sized)
    oci, och = lookup_sorted(pt.vertices, cand)
    crow = jnp.where((och & cand_valid)[:, None], pt.adj[oci], PAD)
    cvv = jnp.broadcast_to(cand[:, None], crow.shape)
    qa = jnp.minimum(cvv, crow)
    qb = jnp.maximum(cvv, crow)
    hit_drop = edge_probe(qa, qb, drop_hi, drop_lo, use_pallas=use_pallas)
    ckeep = jnp.where((crow >= 0) & ~hit_drop, crow, _BIG)

    # 2. insertion neighbor sets grouped by candidate index
    src = jnp.concatenate([ins_hi, ins_lo]).astype(_I32)
    dst = jnp.concatenate([ins_lo, ins_hi]).astype(_I32)
    s_ok = jnp.concatenate([ins_ok, ins_ok])
    gidx, ghit = lookup_sorted(cand, src)
    g = jnp.where(ghit & s_ok & (dst >= 0), gidx, K)
    ins_adj, o2 = scatter_grouped_values(g, dst, K, D)

    # 3. merged candidate member rows (candidate-sized row sort)
    cmerged = jnp.sort(jnp.concatenate(
        [ckeep, jnp.where(ins_adj < 0, _BIG, ins_adj)], axis=1), axis=1)
    ccnt = jnp.sum((cmerged != _BIG).astype(_I32), axis=1)
    o3 = jnp.sum(jnp.where(cand_valid, jnp.maximum(ccnt - D, 0), 0))
    crows = cmerged[:, :D]
    crows = jnp.where(crows == _BIG, PAD, crows)

    # 4. new vertex set: stored vertices survive unless they are
    #    candidates that lost every edge; candidates with members join.
    #    Bitmap + cumsum compaction — no sort.
    mark = jnp.zeros(nv_glob + 1, bool)
    vold = jnp.where((pt.vertices >= 0) & (pt.vertices < nv_glob) & (pt.deg > 0),
                     pt.vertices, nv_glob)
    mark = mark.at[vold].set(True)
    cdump = jnp.where(cand_valid & (cand >= 0) & (cand < nv_glob), cand, nv_glob)
    mark = mark.at[cdump].set(cand_valid & (ccnt > 0))
    vertices, vvalid, o1 = _compact_vec(
        jnp.arange(nv_glob, dtype=_I32), mark[:nv_glob], caps.v_cap, fill=PAD)

    # 5. adjacency in the new layout: gather unchanged rows, overwrite
    #    candidate rows
    oidx, ohit = lookup_sorted(pt.vertices, vertices)
    live = ohit & vvalid
    adj = jnp.where(live[:, None], pt.adj[oidx], PAD)
    deg = jnp.where(live, pt.deg[oidx], 0)
    nidx, nhit = lookup_sorted(vertices, cand)
    wr = jnp.where(cand_valid & nhit, nidx, caps.v_cap)
    adj = jnp.concatenate([adj, jnp.full((1, D), PAD, _I32)], axis=0
                          ).at[wr].set(crows)[: caps.v_cap]
    deg = jnp.concatenate([deg.astype(_I32), jnp.zeros((1,), _I32)]
                          ).at[wr].set(jnp.minimum(ccnt, D))[: caps.v_cap]
    center = vvalid & (vertices >= 0) & (vertices % m == me)

    # 6. canonical edge list: binary-search merge of the (still sorted)
    #    surviving stored list with the (sorted) insertions — the lists
    #    are disjoint, so merge ranks are collision-free. This keeps
    #    the |E|-sized work at one probe + one cumsum instead of
    #    compacting the whole [v_cap · deg_cap] adjacency expansion.
    keep_e = (pt.edge_hi >= 0) & ~edge_probe(pt.edge_hi, pt.edge_lo,
                                             drop_hi, drop_lo,
                                             use_pallas=use_pallas)
    ak, akv, _ = _compact_rows(jnp.stack([pt.edge_hi, pt.edge_lo], axis=1),
                               keep_e, caps.e_cap)
    n_ins = ins_hi.shape[0]
    bk, bkv, _ = _compact_rows(jnp.stack([ins_hi, ins_lo], axis=1),
                               ins_ok, n_ins)
    a_hi = jnp.where(akv, ak[:, 0], _BIG)
    a_lo = jnp.where(akv, ak[:, 1], _BIG)
    b_hi = jnp.where(bkv, bk[:, 0], _BIG)
    b_lo = jnp.where(bkv, bk[:, 1], _BIG)
    pos_a = jnp.arange(caps.e_cap, dtype=_I32) + _lower_bound_pairs(
        a_hi, a_lo, b_hi, b_lo)
    pos_b = jnp.arange(n_ins, dtype=_I32) + _lower_bound_pairs(
        b_hi, b_lo, a_hi, a_lo)
    n_total = jnp.sum(akv.astype(_I32)) + jnp.sum(bkv.astype(_I32))
    o4 = jnp.maximum(n_total - caps.e_cap, 0)
    out = jnp.full((caps.e_cap + 1, 2), PAD, _I32)
    out = out.at[jnp.where(akv & (pos_a < caps.e_cap), pos_a, caps.e_cap)].set(ak)
    out = out.at[jnp.where(bkv & (pos_b < caps.e_cap), pos_b, caps.e_cap)].set(bk)
    part = PaddedPartition(vertices=vertices, center=center, deg=deg, adj=adj,
                           edge_hi=out[:caps.e_cap, 0], edge_lo=out[:caps.e_cap, 1])
    return part, o1 + o2 + o3 + o4


# ---------------------------------------------------------------------------
# Device-resident match maintenance (§VI filter + merge + count)
# ---------------------------------------------------------------------------
#
# The running match set of a streaming pattern lives on the mesh as a
# sharded ``CompTensors``. These primitives are the device halves of
# :func:`repro.core.incremental.filter_deleted`,
# :func:`repro.core.incremental.merge_tables` and
# :meth:`repro.core.vcbc.CompressedTable.count_matches` — same Lemma 6.1
# semantics, padded static shapes, explicit overflow counters. The
# delete-table membership probes route through the Pallas
# ``member_probe`` kernel behind ``use_pallas`` (via :func:`edge_probe`).

def deleted_edge_cols(pattern: Pattern, skel_cols: Sequence[int]):
    """Classify pattern edges for the compressed-form delete filter.

    Every pattern edge has a cover endpoint (the cover is a vertex
    cover), so it is either skeleton–skeleton — returned as a pair of
    *column indices* into ``skel_cols`` — or skeleton–compressed,
    returned as ``(compressed label, skeleton column index)``. This is
    the per-pattern structure :func:`filter_deleted_dev` interprets
    (computed once at trace time, like a plan).
    """
    sidx = {int(c): j for j, c in enumerate(skel_cols)}
    skel_pairs, comp_pairs = set(), set()
    for a, b in pattern.edges:
        if a in sidx and b in sidx:
            skel_pairs.add((sidx[a], sidx[b]))
        elif a in sidx:
            comp_pairs.add((int(b), sidx[a]))
        elif b in sidx:
            comp_pairs.add((int(a), sidx[b]))
        else:
            raise ValueError(f"pattern edge ({a},{b}) has no cover endpoint")
    return tuple(sorted(skel_pairs)), tuple(sorted(comp_pairs))


def filter_deleted_dev(
    tc: CompTensors,
    skel_pairs: Sequence[tuple],
    comp_pairs: Sequence[tuple],
    del_hi: jnp.ndarray,
    del_lo: jnp.ndarray,
    set_cap: int,
    use_pallas: bool = False,
):
    """Drop matches mapping any pattern edge into ``E_d`` (Lemma 6.1).

    Device twin of :func:`repro.core.incremental.filter_deleted`:
    skeleton–skeleton hits invalidate the whole group, skeleton–
    compressed hits shrink the offending per-vertex set (surviving
    values repacked into a valid prefix), and groups whose any set
    empties are invalidated — zero decompression. ``(del_hi, del_lo)``
    is a lex-sorted PAD-tailed edge table (the :func:`edge_probe`
    contract). Returns ``(CompTensors, removed_groups)``; the filter
    never overflows (it only removes).
    """
    valid = tc.valid
    before = jnp.sum(valid.astype(_I32))
    for ia, ib in skel_pairs:
        a = tc.skeleton[:, ia]
        b = tc.skeleton[:, ib]
        hit = edge_probe(jnp.minimum(a, b), jnp.maximum(a, b), del_hi, del_lo,
                         use_pallas=use_pallas)
        valid = valid & ~hit
    keep = {v: tc.sets[v] >= 0 for v in tc.sets}
    for v, j in comp_pairs:
        vals = tc.sets[v]
        sv = jnp.broadcast_to(tc.skeleton[:, j][:, None], vals.shape)
        hit = edge_probe(jnp.minimum(vals, sv), jnp.maximum(vals, sv),
                         del_hi, del_lo, use_pallas=use_pallas)
        keep[v] = keep[v] & ~hit
    sets: Dict[int, jnp.ndarray] = {}
    for v in tc.sets:
        packed, counts = _filter_set_rows(tc.sets[v], keep[v] & valid[:, None],
                                          set_cap)
        sets[v] = packed
        valid = valid & (counts > 0)
    removed = before - jnp.sum(valid.astype(_I32))
    return CompTensors(skeleton=tc.skeleton, valid=valid, sets=sets), removed


def merge_groups(rows: jnp.ndarray, ok: jnp.ndarray,
                 sets_in: Dict[int, jnp.ndarray], group_cap: int, set_cap: int):
    """Regroup rows by identical skeleton, unioning per-vertex sets.

    The one packing primitive behind the cross-chain patch merge, the
    match-store initialization, and :func:`merge_tables_dev`. Returns
    ``(CompTensors, overflow)`` — overflow counts dropped groups beyond
    ``group_cap`` and dropped unique set values beyond ``set_cap``.
    """
    skeleton, gvalid, order, g_eff, ovf = group_rows(rows, ok, group_cap)
    sets_out: Dict[int, jnp.ndarray] = {}
    for v, arr in sets_in.items():
        a = arr[order]                                        # [N, set_cap]
        g_rep = jnp.broadcast_to(g_eff[:, None], a.shape).reshape(-1)
        vals = a.reshape(-1)
        g_rep = jnp.where(vals >= 0, g_rep, group_cap)
        sets_out[v], dropped = scatter_grouped_values(g_rep, vals, group_cap,
                                                      set_cap)
        ovf = ovf + dropped
    return CompTensors(skeleton=skeleton, valid=gvalid, sets=sets_out), ovf


def _pad_set_width(arr: jnp.ndarray, width: int) -> jnp.ndarray:
    if arr.shape[1] >= width:
        return arr
    tail = jnp.full((arr.shape[0], width - arr.shape[1]), PAD, arr.dtype)
    return jnp.concatenate([arr, tail], axis=1)


def merge_tables_dev(tA: CompTensors, tB: CompTensors,
                     group_cap: int, set_cap: int):
    """Union of two compressed tensors of the same pattern (device twin
    of :func:`repro.core.incremental.merge_tables`).

    Groups with equal skeletons are fused and their per-vertex sets
    unioned; the result is a canonical compressed form (lex-sorted
    skeletons, ascending PAD-tailed sets). The two sides may have
    different set widths (e.g. a running store merged with an
    engine-capped patch). Returns ``(CompTensors, overflow)``.

    Contract: each side's *own* valid skeletons must be distinct — the
    form every producer in this module emits (:func:`compress_plain`,
    :func:`merge_groups`, :func:`filter_deleted_dev`). Then every output
    group has at most one source row per side and the set union is a
    pairwise merge of two ascending rows: two keys-only row sorts and
    whole-row gathers of the source rows, instead of routing the full
    ``2·group_cap·set_cap`` (group, value) stream through
    :func:`scatter_grouped_values`, whose stream-wide multi-key sort
    XLA:CPU executes serially (~10× this formulation on the per-batch
    maintain path).
    """
    GA, GB = tA.skeleton.shape[0], tB.skeleton.shape[0]
    rows = jnp.concatenate([tA.skeleton, tB.skeleton], axis=0)
    ok = jnp.concatenate([tA.valid, tB.valid])
    skeleton, gvalid, order, g_eff, ovf = group_rows(rows, ok, group_cap)
    # Source rows of each output group: rows of one group are adjacent
    # in skeleton-sort order and g_eff is nondecreasing over it, so the
    # group's span starts where g_eff first reaches g — at most two
    # rows, one per side, by the distinct-skeleton contract.
    n = rows.shape[0]
    gids = jnp.arange(group_cap, dtype=_I32)
    first = jnp.searchsorted(g_eff, gids)
    second = jnp.clip(first + 1, 0, n - 1)
    has2 = (first + 1 < n) & (g_eff[second] == gids)
    src1 = order[jnp.clip(first, 0, n - 1)]
    src2 = order[second]

    sets_out: Dict[int, jnp.ndarray] = {}
    for v in tA.sets:
        w = max(tA.sets[v].shape[1], tB.sets[v].shape[1])
        a_all = _pad_set_width(tA.sets[v], w)
        b_all = _pad_set_width(tB.sets[v], w)

        def pick(src):
            a = a_all[jnp.clip(src, 0, GA - 1)]
            b = b_all[jnp.clip(src - GA, 0, GB - 1)]
            return jnp.where((src < GA)[:, None], a, b)

        s1 = pick(src1)                                   # [group_cap, w]
        s2 = jnp.where(has2[:, None], pick(src2), PAD)
        cat = jnp.concatenate([s1, s2], axis=1)
        key = jnp.sort(jnp.where(cat < 0, _BIG, cat), axis=1)
        # A value on both sides sits twice, adjacent, in the sorted row:
        # only its first copy is kept, and the repack's own sort pushes
        # the second past ``_BIG`` with the padding.
        prev = jnp.concatenate(
            [jnp.full((group_cap, 1), -2, _I32), key[:, :-1]], axis=1)
        uniq = (key != prev) & (key != _BIG) & gvalid[:, None]
        packed, counts = _filter_set_rows(key, uniq, set_cap)
        sets_out[v] = packed
        ovf = ovf + jnp.sum(jnp.maximum(counts - set_cap, 0))
    return CompTensors(skeleton=skeleton, valid=gvalid, sets=sets_out), ovf


def count_matches_dev(
    tc: CompTensors,
    skel_cols: Sequence[int],
    ord_: Sequence[tuple],
) -> jnp.ndarray:
    """``|M|`` of a compressed tensor without materializing rows.

    Device twin of :meth:`repro.core.vcbc.CompressedTable.count_matches`
    — per group, the number of injective compressed-vertex assignments
    satisfying the symmetry-breaking order, summed over valid groups
    (an ``int32`` scalar; callers ``psum`` across the mesh).

    All decompression constraints are pairwise (injectivity + ord), so
    the count factorizes into pairwise compatibility masks contracted
    with one einsum: exact for any number of compressed vertices, with
    peak memory ``O(G·S²)`` for ≤3. Beyond that the contraction
    intermediate is ``O(G·S^(k-1))``, so for ``k ≥ 4`` the group axis is
    chunked with :func:`jax.lax.map` (:data:`_COUNT_CHUNK` groups per
    step) — peak memory drops to ``O(chunk·S^(k-1))`` at identical
    results (the per-group counts are independent; regression-tested at
    k = 4–5).
    """
    ord_set = {(int(a), int(b)) for a, b in ord_}
    comp = sorted(int(v) for v in tc.sets)
    if not comp:
        return jnp.sum(tc.valid.astype(_I32))
    kv: Dict[int, jnp.ndarray] = {}
    for v in comp:
        vals = tc.sets[v]
        ok = (vals >= 0) & tc.valid[:, None]
        for j, c in enumerate(skel_cols):
            sv = tc.skeleton[:, j][:, None]
            ok = ok & (vals != sv)
            if (v, int(c)) in ord_set:
                ok = ok & (vals < sv)
            if (int(c), v) in ord_set:
                ok = ok & (vals > sv)
        kv[v] = ok
    if len(comp) == 1:
        return jnp.sum(kv[comp[0]].astype(_I32))
    # 'g' is the group axis — keep it out of the per-vertex alphabet.
    alphabet = [c for c in "abcdefhijklmnopqrstuvwxyz"]
    if len(comp) > len(alphabet):
        raise ValueError(f"count_matches_dev supports at most {len(alphabet)} "
                         f"compressed vertices, got {len(comp)}")
    letters = {v: alphabet[i] for i, v in enumerate(comp)}
    operands, subs = [], []
    for i, u in enumerate(comp):
        for w in comp[i + 1:]:
            a, b = tc.sets[u], tc.sets[w]
            ok = (kv[u][:, :, None] & kv[w][:, None, :]
                  & (a[:, :, None] != b[:, None, :]))
            if (u, w) in ord_set:
                ok = ok & (a[:, :, None] < b[:, None, :])
            if (w, u) in ord_set:
                ok = ok & (a[:, :, None] > b[:, None, :])
            operands.append(ok.astype(_I32))
            subs.append(f"g{letters[u]}{letters[w]}")
    # greedy path: the optimal-path search is super-exponential in the
    # number of operands (k·(k-1)/2 pair masks) and stalls trace time
    # beyond k ≈ 6; greedy contracts pairwise and stays near-optimal
    # for this regular mask structure.
    expr = ",".join(subs) + "->g"
    if len(comp) < 4:
        return jnp.sum(jnp.einsum(expr, *operands, optimize="greedy"))
    # k ≥ 4: the contraction intermediate grows as O(G·S^(k-1)) — chunk
    # the (independent) group axis so peak memory is bounded by the
    # chunk, not the group cap.
    G = operands[0].shape[0]
    chunk = min(_COUNT_CHUNK, G)
    n_chunks = -(-G // chunk)
    pad = n_chunks * chunk - G

    def pad_op(op):
        if pad:
            # zero rows contribute zero matches — padding is free
            op = jnp.concatenate(
                [op, jnp.zeros((pad,) + op.shape[1:], op.dtype)], axis=0)
        return op.reshape((n_chunks, chunk) + op.shape[1:])

    per_chunk = jax.lax.map(
        lambda ops: jnp.sum(jnp.einsum(expr, *ops, optimize="greedy")),
        tuple(pad_op(op) for op in operands))
    return jnp.sum(per_chunk)
