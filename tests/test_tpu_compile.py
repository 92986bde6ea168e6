"""The scoring path's kernels compile for a TPU v5e.

Interpret mode runs every Pallas kernel on the CPU, but it cannot see what
the chip's compiler refuses: fast-memory (VMEM) overruns, unaligned
blocks, programs that do not partition.  These tests compile each kernel
of the scoring path at real widths for a described ``v5e:2x2`` host -- no
chip is attached and nothing runs -- so such a refusal fails here.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU compiler's library.
"""

import math
import re

import numpy as np
import pytest

from repro.core.kernels_pallas import (
    _M_ROWS,
    _P_ROWS,
    PallasBackend,
    _variant_tile,
)
from repro.core.kernels_xp import JaxBackend, MachineArrays, ProfileArrays

V = 65_536  # one streamed shard (sweep.STREAM_SHARD_VARIANTS)


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip can be written to the
    # persistent cache but not read back; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:4]), ("variants",))


def _f32(shape, sharding):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _kernel_ran(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("apps", [6, 128, 1000])
def test_pallas_congruence_compiles(one_chip, apps):
    be = PallasBackend(interpret=False)
    tile = _variant_tile(apps, V)
    compiled = be._congruence_fn().lower(
        _f32((_P_ROWS, apps), one_chip), _f32((_M_ROWS, V), one_chip),
        timing_model="serial", eps=1e-9, clamp=True, tile=tile).compile()
    assert _kernel_ran(compiled)
    assert compiled.memory_analysis().output_size_in_bytes == 8 * apps * V * 4


def test_pallas_step_time_compiles(one_chip):
    be = PallasBackend(interpret=False)
    compiled = be._step_time_fn().lower(
        _f32((_P_ROWS - 1, 128), one_chip), _f32((_M_ROWS, V), one_chip),
        timing_model="overlap", tile=_variant_tile(128, V)).compile()
    assert _kernel_ran(compiled)


def test_pallas_default_beta_compiles(one_chip):
    be = PallasBackend(interpret=False)
    compiled = be._default_beta_fn().lower(
        _f32((_P_ROWS - 1, 128), one_chip),
        _f32((_M_ROWS, 1), one_chip)).compile()
    assert _kernel_ran(compiled)


def test_jax_x64_congruence_compiles(one_chip):
    import jax
    import jax.numpy as jnp

    be = JaxBackend()
    with be._x64():
        def f64(n):
            return jax.ShapeDtypeStruct((n,), jnp.float64, sharding=one_chip)

        compiled = be._congruence_fn().lower(
            ProfileArrays(*(f64(128) for _ in ProfileArrays._fields)),
            MachineArrays(*(f64(V) for _ in MachineArrays._fields)),
            f64(128), timing_model="serial", eps=1e-9, clamp=True).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= 8 * 128 * V * 8


def test_pallas_sharded_stats_compiles(mesh4):
    from jax.sharding import NamedSharding, PartitionSpec

    be = PallasBackend(interpret=False)
    local = V // mesh4.size
    fn = be._sharded_stats_fn(mesh4, V, local, _variant_tile(128, local),
                              "serial", True)
    compiled = fn.lower(
        _f32((_P_ROWS, 128), NamedSharding(mesh4, PartitionSpec())),
        _f32((_M_ROWS, V), NamedSharding(mesh4,
                                         PartitionSpec(None, "variants"))),
    ).compile()
    assert _kernel_ran(compiled)
    # only the O(V) means and (ndev, A) min/argmin rows leave the devices
    assert compiled.memory_analysis().output_size_in_bytes < 8 * 128 * local


def test_jax_sharded_stats_compiles(mesh4):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    be = JaxBackend()
    split = NamedSharding(mesh4, PartitionSpec("variants"))
    rep = NamedSharding(mesh4, PartitionSpec())
    with be._x64():
        def f64(n, sharding):
            return jax.ShapeDtypeStruct((n,), jnp.float64, sharding=sharding)

        compiled = be._sharded_stats_fn(V, V).lower(
            ProfileArrays(*(f64(128, rep) for _ in ProfileArrays._fields)),
            MachineArrays(*(f64(V, split) for _ in MachineArrays._fields)),
            f64(128, rep), timing_model="serial", clamp=True).compile()
    # The partitioner combines per-device minima: its collectives move
    # (ndev, A) rows, never the (A, V) score tensor.
    moved = [math.prod(int(d) for d in dims.split(",") if d)
             for dims in re.findall(
                 r"= \w+\[([\d,]*)\][^=]*? all-(?:gather|reduce)\(",
                 compiled.as_text())]
    assert moved and max(moved) <= mesh4.size * 128
