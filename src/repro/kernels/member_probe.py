"""Sorted-table membership probe — edge-existence and join-key probing.

DDSL's hot predicate is ``code(u, v) ∈ E_j`` (match filtering, Lemma 6.1
checks, CC-join probes). Edge codes are pairs of vertex ids; TPUs are
32-bit-native, so codes travel as two int32 lanes ``(hi, lo)`` =
``(min(u,v), max(u,v))`` and the kernel compares both planes.

Layout: queries and table are each laid out lane-dense as ``[rows, 128]``
int32 planes, tiled in ``(8·k, 128)`` blocks. The grid is 2-D
``(query_tiles, table_tiles)`` with an OR accumulation into the revisited
int32 output block — grid steps on TPU execute sequentially, so
read-modify-write across the table dimension is safe. Inside a step,
every table row is broadcast over the sublanes and rotated through all
128 lane offsets (``pltpu.roll``), so each query lane meets each table
entry once; all compares are 2-D int32, never an i1 3-D broadcast (which
Mosaic cannot relayout).

This is a *membership* probe (equality-any), not a binary search: work
is ``n_q · n_t`` compares against a search's ``n_q · log n_t`` gathers,
so it only pays off for small tables. Pad convention: ``(-1, -1)``; a
padded query reports False, and a padded table entry can only equal a
padded query.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["member_probe_pallas"]

_LANES = 128
_SUBLANES = 8


def _kernel(qhi_ref, qlo_ref, thi_ref, tlo_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)

    qhi = qhi_ref[...]
    qlo = qlo_ref[...]

    def table_row(r, acc):
        th = jnp.broadcast_to(thi_ref[pl.ds(r, 1), :], (_SUBLANES, _LANES))
        tl = jnp.broadcast_to(tlo_ref[pl.ds(r, 1), :], (_SUBLANES, _LANES))
        for s in range(_LANES):
            rh = pltpu.roll(th, s, 1) if s else th
            rl = pltpu.roll(tl, s, 1) if s else tl
            hit = (qhi == rh[0:1, :]) & (qlo == rl[0:1, :])
            acc = acc | hit.astype(jnp.int32)
        return acc

    acc = lax.fori_loop(0, thi_ref.shape[0], table_row,
                        jnp.zeros(qhi.shape, jnp.int32))
    o_ref[...] = o_ref[...] | acc


def _rows(n: int, tile: int):
    """``(padded_rows, block_rows)`` of an ``n``-entry lane-dense plane.

    A block is a multiple of 8 rows, or the whole (short) plane.
    """
    rows = max(-(-n // _LANES), 1)
    blk = max(tile // _LANES // _SUBLANES, 1) * _SUBLANES
    if rows <= blk:
        return rows, rows
    return -(-rows // blk) * blk, blk


def _plane(x: jax.Array, rows: int) -> jax.Array:
    x = x.astype(jnp.int32)
    return jnp.pad(x, (0, rows * _LANES - x.shape[0]),
                   constant_values=-1).reshape(rows, _LANES)


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_t", "interpret"))
def member_probe_pallas(
    q_hi: jax.Array,
    q_lo: jax.Array,
    t_hi: jax.Array,
    t_lo: jax.Array,
    *,
    tile_q: int = 8192,
    tile_t: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """out[i] = (q_hi[i], q_lo[i]) ∈ zip(t_hi, t_lo). int32 lanes, bool out.

    ``tile_q`` / ``tile_t`` are entries per block, rounded to whole
    ``(8, 128)`` tiles.
    """
    n = q_hi.shape[0]
    q_rows, bq = _rows(n, tile_q)
    t_rows, bt = _rows(t_hi.shape[0], tile_t)
    q_spec = pl.BlockSpec((bq, _LANES), lambda i, j: (i, 0))
    t_spec = pl.BlockSpec((bt, _LANES), lambda i, j: (j, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(q_rows // bq, t_rows // bt),
        in_specs=[q_spec, q_spec, t_spec, t_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((q_rows, _LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_plane(q_hi, q_rows), _plane(q_lo, q_rows),
      _plane(t_hi, t_rows), _plane(t_lo, t_rows))
    valid_q = ~((q_hi == -1) & (q_lo == -1))
    return (out.reshape(-1)[:n] > 0) & valid_q
