"""Jit'd public wrappers around the Pallas kernels.

Each op:
- dispatches to the Pallas kernel (compiled on TPU; ``interpret=True``
  on the CPU backend, where the tests run);
- can be forced to the pure-jnp reference with ``backend="ref"`` — the
  roofline lowering (``launch/steps.py``) uses ``ref`` so the HLO
  reflects the XLA-native formulation, and the Pallas path is
  benchmarked separately.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import autotune, ref
from .embedding_bag import embedding_bag_pallas
from .flash_attention import flash_attention_pallas
from .member_probe import member_probe_pallas
from .segment_sum import segment_sum_pallas
from .set_intersect import set_intersect_pallas

__all__ = [
    "set_intersect",
    "member_probe",
    "segment_sum",
    "embedding_bag",
    "flash_attention",
    "default_backend",
]


def default_backend() -> str:
    """Compiled kernels on TPU; interpret mode on the CPU backend, which
    exists for tests. Any other platform has no kernel path and raises."""
    plat = jax.default_backend()
    if plat == "tpu":
        return "pallas"
    if plat == "cpu":
        return "pallas_interpret"
    raise RuntimeError(f"no Pallas kernel path for platform {plat!r}")


def _interpret(backend: str) -> bool:
    return backend != "pallas"


def set_intersect(
    a: jax.Array,
    b: jax.Array,
    *,
    pad: int,
    backend: str | None = None,
    tile_g: int | None = None,
) -> jax.Array:
    backend = backend or default_backend()
    if backend == "ref":
        return ref.set_intersect_ref(a, b, pad)
    if tile_g is None:
        tile_g = autotune.set_intersect_tiles(a.shape[0])
    return set_intersect_pallas(a, b, pad=pad, tile_g=tile_g,
                                interpret=_interpret(backend))


def member_probe(
    q_hi: jax.Array,
    q_lo: jax.Array,
    t_hi: jax.Array,
    t_lo: jax.Array,
    *,
    backend: str | None = None,
    tile_q: int | None = None,
    tile_t: int | None = None,
) -> jax.Array:
    backend = backend or default_backend()
    if backend == "ref":
        return ref.member_probe_ref(q_hi, q_lo, t_hi, t_lo)
    if tile_q is None or tile_t is None:
        tq, tt = autotune.member_probe_tiles(q_hi.shape[0], t_hi.shape[0])
        tile_q = tq if tile_q is None else tile_q
        tile_t = tt if tile_t is None else tile_t
    return member_probe_pallas(q_hi, q_lo, t_hi, t_lo, tile_q=tile_q,
                               tile_t=tile_t, interpret=_interpret(backend))


def segment_sum(
    data: jax.Array, segment_ids: jax.Array, num_segments: int, *, backend: str | None = None
) -> jax.Array:
    backend = backend or default_backend()
    if backend == "ref":
        return ref.segment_sum_ref(data, segment_ids, num_segments)
    return segment_sum_pallas(data, segment_ids, num_segments, interpret=_interpret(backend))


def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    bag_ids: jax.Array,
    num_bags: int,
    *,
    backend: str | None = None,
) -> jax.Array:
    backend = backend or default_backend()
    if backend == "ref":
        return ref.embedding_bag_ref(table, indices, bag_ids, num_bags)
    # Kernel contract: sorted by bag id; bags may be empty → mask after.
    order = jnp.argsort(bag_ids, stable=True)
    idx = indices[order]
    bag = bag_ids[order]
    out = embedding_bag_pallas(table, idx, bag, num_bags, interpret=_interpret(backend))
    counts = jax.ops.segment_sum(jnp.ones_like(bag), bag, num_segments=num_bags)
    return jnp.where(counts[:, None] > 0, out, 0).astype(table.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    backend: str | None = None,
    tile_q: int = 128,
    tile_k: int = 128,
) -> jax.Array:
    backend = backend or default_backend()
    if backend == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, q_offset=q_offset)
    return flash_attention_pallas(
        q, k, v, causal=causal, q_offset=q_offset,
        tile_q=tile_q, tile_k=tile_k, interpret=_interpret(backend),
    )
