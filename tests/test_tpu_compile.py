"""The device program compiles for TPU v5e, without a chip.

``repro.sweep.device`` evaluates a whole grid as one
``jit(vmap(_group_kernel))`` program in float64, and shards it with
``pmap`` over the local chips. Here the TPU compiler, which is installed
with JAX, compiles that program for described v5e devices: the padded
``(G, S, K)`` buckets the full-size fig1 / fig3 / fig4 / exp5 / perf
grids reach on one chip (``chip_smoke.py`` prints them), and the perf
grid's 4-way split over a 2x2 host. Nothing runs, so this says nothing
about results or times; it catches what the chip's compiler refuses.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.sim.execmodel import PARAMS_FIELDS
from repro.sweep.device import _group_kernel

#: (G, S, K) per grid at full size, with x64 on as the dispatch runs it
ONE_CHIP_BUCKETS = {
    "fig1": (16, 32768, 1),
    "fig3": (32, 16384, 1),
    "fig4": (8, 32768, 1),
    "exp5": (16, 65536, 1),
    "perf": (16, 8192, 256),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _arg_shapes(g, s, k, lead=(), sharding=None):
    """ShapeDtypeStructs of the ten padded arguments, as
    ``execute_device_grid`` builds them (float64 but the f32 power
    parameters)."""
    def sd(shape, dtype=jnp.float64):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)
    return ((sd((g, s)),) * 4
            + (sd((g, len(PARAMS_FIELDS))), sd((g, 5), jnp.float32),
               sd((g,)), sd((g,)), sd((g, k)), sd((g, k))))


@pytest.mark.parametrize("grid", sorted(ONE_CHIP_BUCKETS))
def test_grid_program_compiles_for_one_v5e(topo, no_compile_cache, grid):
    from jax.sharding import SingleDeviceSharding

    g, s, k = ONE_CHIP_BUCKETS[grid]
    with jax.enable_x64(True):
        args = _arg_shapes(g, s, k,
                           sharding=SingleDeviceSharding(topo.devices[0]))
        compiled = jax.jit(jax.vmap(_group_kernel)).lower(*args).compile()
    mem = compiled.memory_analysis()
    # the composition columns alone are 4*G*S float64s: the program
    # really is the f64 one, and it fits one chip's 16 GB
    assert mem.argument_size_in_bytes >= 4 * g * s * 8
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_perf_grid_pmap_splits_four_ways(topo, no_compile_cache):
    # 12 perf groups pad to 16, so four chips take (4, 4) each
    g, s, k = ONE_CHIP_BUCKETS["perf"]
    d = len(topo.devices)
    assert d == 4
    with jax.enable_x64(True):
        args = _arg_shapes(g // d, s, k, lead=(d,))
        compiled = jax.pmap(jax.vmap(_group_kernel),
                            devices=topo.devices).lower(*args).compile()
    assert compiled.memory_analysis().argument_size_in_bytes \
        >= 4 * (g // d) * s * 8
