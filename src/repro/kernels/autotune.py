"""Tile autotune table for the Pallas kernels.

Block/tile sizes for :func:`repro.kernels.ops.member_probe` and
:func:`repro.kernels.ops.set_intersect` keyed by (backend platform,
shape bucket). The kernels take their tiles as static arguments, so
every distinct tile choice is a distinct compilation — the table keeps
the choices coarse (power-of-two shape buckets) and deterministic so
jitted callers hit a handful of stable variants instead of recompiling
per exact cap shape.

The TPU rows are tiles that compile for a TPU v5e at every bucket
(``tests/test_tpu_compile.py`` compiles each against a described v5e
chip); their speed is not measured yet, so they are a starting point
for a sweep on the chip (``benchmarks/bench_kernels.py --sweep-tiles``),
not its result. ``member_probe`` tiles are entries per block and round
to whole ``(8, 128)`` int32 tiles; ``set_intersect``'s ``tile_g`` is an
upper bound that the kernel clamps to its VMEM budget. The
CPU/interpret rows only bound working-set size — interpret mode is a
parity path, not a perf path (see :func:`default_use_pallas`).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

__all__ = [
    "default_use_pallas",
    "member_probe_tiles",
    "rows_from_sweep",
    "set_intersect_tiles",
    "platform",
]


def platform() -> str:
    """Current XLA backend platform name; raises if no backend starts."""
    return jax.default_backend()


def default_use_pallas(plat: Optional[str] = None) -> bool:
    """Platform default for ``EngineCaps.use_pallas`` — the one place the
    served path's kernel choice is made.

    On for TPU: on one v5e, 2^20 edge probes against an 83,712-entry
    table took 0.07 s in the all-pairs ``member_probe`` and 0.29 s in
    the engine's binary search, and the service's steady batch was
    faster with the kernel in the one batch where both ran (measurements
    in CHANGES.md; the binary-search run was cut short). Off on the CPU
    backend, where the kernels only run in interpret mode.
    """
    return (plat if plat is not None else platform()) == "tpu"


# (platform, kernel) → ascending (shape-bucket upper bound, tiles);
# ``None`` bound = catch-all. Unknown platforms fall back to "cpu" rows.
_MEMBER_PROBE = {
    # bucket key: padded edge-table length n_t → (tile_q, tile_t)
    "tpu": ((4096, (4096, 1024)), (32768, (8192, 2048)), (None, (8192, 4096))),
    "cpu": ((4096, (1024, 2048)), (None, (2048, 4096))),
}
_SET_INTERSECT = {
    # bucket key: group count n_g → (tile_g,)
    "tpu": ((1024, (64,)), (8192, (128,)), (None, (256,))),
    "cpu": ((None, (256,)),),
}


def _lookup(table, plat: Optional[str], n: int):
    rows = table.get(plat if plat is not None else platform(), table["cpu"])
    for bound, tiles in rows:
        if bound is None or n <= bound:
            return tiles
    return rows[-1][1]  # pragma: no cover - catch-all row always present


def member_probe_tiles(n_q: int, n_t: int,
                       plat: Optional[str] = None) -> Tuple[int, int]:
    """``(tile_q, tile_t)`` for an ``n_q`` query / ``n_t`` table probe.

    Bucketed by the table length — the table side is what gets swept
    per query tile, so it dominates the kernel's working set.
    """
    del n_q  # queries are tiled independently; the table side dominates
    return _lookup(_MEMBER_PROBE, plat, n_t)


def set_intersect_tiles(n_groups: int, plat: Optional[str] = None) -> int:
    """``tile_g`` (group-axis tile) for an ``n_groups``-row intersection."""
    return _lookup(_SET_INTERSECT, plat, n_groups)[0]


def rows_from_sweep(doc: dict) -> dict:
    """Re-record the bucket tables from a ``--sweep-tiles`` artifact.

    ``doc`` is the JSON written by ``benchmarks.bench_kernels
    --sweep-tiles``: per-cell timings of every (shape bucket × candidate
    tile). Returns the winning rows in exactly the `_MEMBER_PROBE` /
    `_SET_INTERSECT` literal shape, ready to paste as this platform's
    entry::

        {"member_probe": [[4096, [512, 2048]], ..., [None, [1024, 4096]]],
         "set_intersect": [[1024, [256]], ..., [None, [1024]]]}

    The last (largest) bucket becomes the ``None`` catch-all row, same
    convention as the shipped tables.
    """

    def winners(cells, bucket_key, tile_keys):
        best = {}
        for c in cells:
            b = int(c[bucket_key])
            if b not in best or float(c["us"]) < float(best[b]["us"]):
                best[b] = c
        rows = []
        for i, b in enumerate(sorted(best)):
            tiles = [int(best[b][k]) for k in tile_keys]
            bound = None if i == len(best) - 1 else b
            rows.append([bound, tiles])
        return rows

    return {
        "member_probe": winners(doc.get("member_probe", ()),
                                "n_t", ("tile_q", "tile_t")),
        "set_intersect": winners(doc.get("set_intersect", ()),
                                 "n_g", ("tile_g",)),
    }
