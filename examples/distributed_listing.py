"""Distributed SPMD listing + incremental update on a device mesh.

Runs the jitted shard_map steps on every device JAX sees (one NP
partition per device) and cross-checks against the host engine. On a
CPU-only machine, fake several devices with XLA's host-device flag:

    PYTHONPATH=src python examples/distributed_listing.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python examples/distributed_listing.py
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

import repro
from repro.core import DDSL, build_np_storage, symmetry_break
from repro.core.cost import CostModel
from repro.core.ddsl import choose_cover
from repro.core.estimator import GraphStats
from repro.core.join_tree import minimum_unit_decomposition, optimal_join_tree
from repro.core.pattern import PATTERN_LIBRARY
from repro.data.graphs import rmat_graph, sample_update
from repro.dist import jax_engine as je
from repro.dist import sharded


def main() -> None:
    repro.use_compile_cache()
    m = jax.device_count()
    mesh = jax.make_mesh((m,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    graph = rmat_graph(7, 320, seed=0)
    pattern = PATTERN_LIBRARY["q1_square"]
    ord_ = symmetry_break(pattern)
    stats = GraphStats.of(graph)
    cover = choose_cover(pattern, ord_, stats)
    tree = optimal_join_tree(pattern, cover, CostModel(cover, ord_, stats))
    prog = sharded.build_tree_program(tree, cover, ord_)
    units = minimum_unit_decomposition(pattern, cover)

    caps = je.EngineCaps(v_cap=128, deg_cap=64, e_cap=1024, match_cap=8192,
                         group_cap=4096, set_cap=64, pair_cap=256)
    storage = build_np_storage(graph, m)
    pt = sharded.stack_partitions(storage, caps)
    pt = jax.device_put(pt, jax.tree.map(lambda s: NamedSharding(mesh, s),
                                         sharded.partition_specs(mesh)))

    print("compiling distributed list_step ...")
    list_step = sharded.make_list_step(prog, mesh, caps)
    out, diag = list_step(pt)
    host = DDSL(graph, pattern, m=m, cover=cover)
    host.initial()
    print(f"distributed groups={int(diag['matches_lower_bound'])} "
          f"overflow={int(diag['overflow'])} | host |M|={host.count()}")

    update = sample_update(graph, 4, 4, seed=2)
    print("compiling distributed update_step ...")
    upd_step = sharded.make_update_step(prog, units, mesh, caps,
                                        sharded.UpdateShapes(4, 4))
    pt2, patch, diag2 = upd_step(
        pt, jnp.asarray(update.add, jnp.int32), jnp.asarray(update.delete, jnp.int32)
    )
    host.apply(update)
    print(f"patch groups={int(diag2['patch_groups'])} overflow={int(diag2['overflow'])} "
          f"| host |M(p,d')|={host.count()}")
    print("distributed run complete")


if __name__ == "__main__":
    main()
