"""The Pallas kernels compile for a TPU v5e chip, at every autotune bucket.

Interpret mode (every other kernel test) accepts layouts and VMEM use
that the TPU compiler refuses. These tests compile each kernel for a
*described* v5e chip — the TPU compiler is installed even where no chip
is attached — and check that the kernel is really in the program.
Nothing runs; results are checked by ``tests/test_kernels.py`` and on
the chip by ``chip_smoke.py``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so a module-level call would break
every other test worker's collection.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.member_probe import member_probe_pallas
from repro.kernels.set_intersect import set_intersect_pallas

PAD = -1


def _bucket_sizes(rows, catch_all):
    return [bound if bound is not None else catch_all for bound, _ in rows]


MP_TABLES = _bucket_sizes(autotune._MEMBER_PROBE["tpu"], 262144)
SI_GROUPS = _bucket_sizes(autotune._SET_INTERSECT["tpu"], 65536)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel_compiled(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_q", [1000, 65536])
@pytest.mark.parametrize("n_t", MP_TABLES)
def test_member_probe_compiles_for_v5e(one_chip, n_t, n_q):
    tq, tt = autotune.member_probe_tiles(n_q, n_t, plat="tpu")
    q, t = _i32((n_q,), one_chip), _i32((n_t,), one_chip)
    _assert_kernel_compiled(
        lambda a, b, c, d: member_probe_pallas(a, b, c, d, tile_q=tq,
                                               tile_t=tt),
        q, q, t, t)


@pytest.mark.parametrize("ca,cb", [(64, 64), (16, 8), (1, 64), (760, 760)])
@pytest.mark.parametrize("n_g", SI_GROUPS)
def test_set_intersect_compiles_for_v5e(one_chip, n_g, ca, cb):
    tg = autotune.set_intersect_tiles(n_g, plat="tpu")
    _assert_kernel_compiled(
        lambda a, b: set_intersect_pallas(a, b, pad=PAD, tile_g=tg),
        _i32((n_g, ca), one_chip), _i32((n_g, cb), one_chip))
