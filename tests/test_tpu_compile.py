"""The main path's programs compile for a TPU v5e, at real sizes.

Nothing here runs on a chip: the TPU compiler installed with JAX
compiles for a ``v5e:2x2`` topology that is described, not attached
(the ``on-chip-measurement`` guide, section 2).  That catches what
interpret mode and the CPU backend cannot — Mosaic refusing a kernel, a
program that does not fit the device's memory — before a chip run
spends time on it.  The topology is described only inside the fixture
below (never at import): one process at a time may load the TPU
library, and a worker that cannot describe it skips these tests.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prior_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else libtpu logs under /tmp
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prior_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prior_cache)
    compilation_cache.reset_cache()
    if prior_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prior_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state_shapes(capacity, bins, sharding_of):
    from heatmap_tpu.engine.state import init_state

    st = jax.eval_shape(lambda: init_state(capacity, bins))
    return jax.tree_util.tree_map(
        lambda a: _shape(a.shape, a.dtype, sharding_of(a)), st)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert need < HBM_BYTES, f"{need / 1e9:.2f} GB does not fit one v5e"
    return need


@pytest.mark.parametrize("res", [7, 8, 9, 10])
def test_pallas_snap_geometry_lowers(one_chip, res):
    """The Mosaic kernel at a 2^19-point batch, per resolution."""
    from heatmap_tpu.hexgrid.pallas_kernel import _snap_geometry

    x = _shape((1 << 19,), jnp.float32, one_chip)
    compiled = _snap_geometry.lower(x, x, res=res).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("capacity", [1 << 20, 1 << 21])
def test_aggregate_batch_backfill_shape(one_chip, capacity):
    """synthetic_backfill's fold (res 9, batch 2^19, the default 64 speed
    bins) compiles and fits one chip: on its configured 2^20-row slab
    and on the 2^21 rows the runtime grows it to, where the slab rebuild
    gathers kept rows through the inverse routing map."""
    from heatmap_tpu.engine.step import AggParams, aggregate_batch

    batch = 1 << 19
    params = AggParams(res=9, window_s=300, emit_capacity=batch)
    st = _state_shapes(capacity, 64, lambda a: one_chip)
    f32 = _shape((batch,), jnp.float32, one_chip)
    compiled = jax.jit(functools.partial(aggregate_batch, params=params)) \
        .lower(st, f32, f32, f32, _shape((batch,), jnp.int32, one_chip),
               _shape((batch,), jnp.bool_, one_chip),
               _shape((), jnp.int32, one_chip)).compile()
    _fits(compiled)


def test_kalman_scan_entity_table(one_chip):
    """The Kalman reducer's rounds scan over the configured entity
    table (HEATMAP_ENTITY_CAPACITY, 2^17) at 32 rounds per batch."""
    from heatmap_tpu.config import Config
    from heatmap_tpu.infer.kalman import _scan_fn

    m, k = Config.entity_capacity, 32
    f32 = jnp.float32
    arr = functools.partial(_shape, sharding=one_chip)
    compiled = _scan_fn().lower(
        arr((m, 4), f32), arr((m, 4, 4), f32), arr((k, m, 2), f32),
        arr((k, m), f32), arr((k, m), jnp.bool_), arr((k, m), jnp.bool_),
        *[arr((), f32)] * 5).compile()
    _fits(compiled)
