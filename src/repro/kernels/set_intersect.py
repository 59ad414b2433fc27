"""Padded-set intersection kernel — the DDSL join/listing hot spot.

TPU adaptation of the paper's adjacency-intersection inner loop: instead
of pointer-chasing sorted-merge (CPU/GPU idiom), each grid step loads a
``(TG, TA)`` tile of query sets and the matching ``(TG, CB)`` tile of
target sets into VMEM and evaluates a broadcast compare-reduce
``any(a[:, :, None] == b[:, None, :])`` on the VPU, 128 target lanes at
a time. Set capacities are the static paddings the JAX engine derives
from the paper's match-size estimator.

VMEM: the ``(TG, TA, 128)`` compare is the kernel's working set, so the
group tile is clamped to a fixed VMEM budget given the set widths
(:func:`vmem_tile_g`) — whatever tile the autotune table asks for, the
kernel stays well inside the 16 MiB scoped-VMEM limit of a TPU v5e.

Used by: compressed-vertex-set intersection in the distributed CC-join
(Alg. 2 line 4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["set_intersect_pallas", "vmem_tile_g"]

_LANES = 128
_SUBLANES = 8
# Bytes the (TG, TA, 128) compare temporaries may take per grid step.
_VMEM_BUDGET = 4 * 1024 * 1024
# Query-set columns per grid step once a set is wider than this.
_MAX_TILE_A = 256


def _up(x: int, k: int) -> int:
    return -(-x // k) * k


def vmem_tile_g(tile_g: int, g: int, tile_a: int) -> int:
    """Largest group tile ≤ ``tile_g`` whose compare fits the budget.

    Three live ``(TG, TA, 128)`` 32-bit temporaries per step (compare,
    pad mask, reduction), with ``TA`` padded to whole sublane tiles. The
    result is a multiple of 8, or all ``g`` groups when they fit.
    """
    per_group = 3 * _up(tile_a, _SUBLANES) * _LANES * 4
    fit = max(_VMEM_BUDGET // per_group // _SUBLANES * _SUBLANES, _SUBLANES)
    tg = min(tile_g, fit)
    if g <= tg:
        return max(g, 1)
    return max(tg // _SUBLANES * _SUBLANES, _SUBLANES)


def _kernel(a_ref, b_ref, o_ref, *, pad: int):
    a = a_ref[...]
    hit = jnp.zeros(a.shape, jnp.bool_)
    for c0 in range(0, b_ref.shape[1], _LANES):
        b = b_ref[:, c0:c0 + _LANES]
        eq = (a[:, :, None] == b[:, None, :]) & (b != pad)[:, None, :]
        hit = hit | jnp.any(eq, axis=-1)
    o_ref[...] = (hit & (a != pad)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("pad", "tile_g", "interpret"))
def set_intersect_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    pad: int,
    tile_g: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """mask[g, i] = a[g, i] ∈ b[g, :] (pad-aware). a: [G, CA] b: [G, CB] int32."""
    g, ca = a.shape
    if ca > _MAX_TILE_A:
        tile_a = _LANES
        pad_a = (-ca) % _LANES
        a = jnp.pad(a, ((0, 0), (0, pad_a)), constant_values=pad)
    else:
        tile_a = ca
    if b.shape[1] > _LANES:
        b = jnp.pad(b, ((0, 0), (0, (-b.shape[1]) % _LANES)), constant_values=pad)
    tile_g = vmem_tile_g(tile_g, g, tile_a)
    pad_g = (-g) % tile_g
    if pad_g:
        a = jnp.pad(a, ((0, pad_g), (0, 0)), constant_values=pad)
        b = jnp.pad(b, ((0, pad_g), (0, 0)), constant_values=pad)
    gp, cap = a.shape
    out = pl.pallas_call(
        functools.partial(_kernel, pad=pad),
        grid=(gp // tile_g, cap // tile_a),
        in_specs=[
            pl.BlockSpec((tile_g, tile_a), lambda i, k: (i, k)),
            pl.BlockSpec((tile_g, b.shape[1]), lambda i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_g, tile_a), lambda i, k: (i, k)),
        out_shape=jax.ShapeDtypeStruct((gp, cap), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(a, b)
    return out[:g, :ca].astype(jnp.bool_)
