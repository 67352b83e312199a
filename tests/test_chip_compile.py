"""Compile-only checks of the extraction path for one TPU v5e chip.

The TPU compiler compiles here for a described, unattached v5e chip, so
what Mosaic or XLA would refuse on the chip (block shapes, SMEM size,
f64 operands) fails this file without chip time.  Shapes are those of
the O1280 archive of the paper's Table 1: 8 steps × 20 levels ×
6,599,680 points in float32, 4.22 GB.  Nothing runs, so these tests say
nothing about results or speed.

The topology is described inside a fixture, never while a module is
imported, so every pytest-xdist worker collects the same tests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gather import kernel as gk
from repro.kernels.gather.ops import BURST_BLOCK
from repro.kernels.plan import ref as plan_ref

O1280_ELEMENTS = 8 * 20 * 6_599_680
PAYLOAD_BYTES = O1280_ELEMENTS * 4
ROWS = 65_536            # gathered 128-element windows: 32 MiB out


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_reads_in_place(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= PAYLOAD_BYTES
    # no copy of the payload: the (N/128, 128) view is a bitcast
    assert mem.temp_size_in_bytes < PAYLOAD_BYTES // 100


def test_gather_runs_compiles_at_o1280(one_chip):
    compiled = gk._gather_runs.lower(
        _spec((O1280_ELEMENTS,), jnp.float32, one_chip),
        _spec((ROWS,), jnp.int32, one_chip),
        block=BURST_BLOCK, interpret=False).compile()
    _assert_kernel_reads_in_place(compiled)


def test_gather_rows_compiles_at_o1280(one_chip):
    compiled = gk._gather_rows.lower(
        _spec((O1280_ELEMENTS // BURST_BLOCK, BURST_BLOCK), jnp.float32,
              one_chip),
        _spec((gk.MAX_ROWS_PER_CALL,), jnp.int32, one_chip),
        interpret=False).compile()
    _assert_kernel_reads_in_place(compiled)


def test_served_take_compiles_at_o1280(one_chip):
    compiled = jax.jit(lambda flat, offs: jnp.take(flat, offs)).lower(
        _spec((O1280_ELEMENTS,), jnp.float32, one_chip),
        _spec((ROWS,), jnp.int32, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < PAYLOAD_BYTES


def test_plan_pipeline_compiles_in_f64(one_chip):
    """The jnp planning pipeline the device planner runs, in float64, on
    the 640 × 1280 irregular cube; the Pallas form cannot take f64."""
    j, v, n0, n1, max_rows = 8, 24, 640, 1280, 64
    with jax.enable_x64(True):
        f64 = lambda shape: _spec(shape, jnp.float64, one_chip)  # noqa: E731
        i32 = lambda shape: _spec(shape, jnp.int32, one_chip)  # noqa: E731
        compiled = plan_ref.plan_runs_2d.lower(
            f64((j, v, 2)), _spec((j, v), np.bool_, one_chip), i32((j,)),
            f64((n0,)), i32((n0,)), f64((n1,)), f64((4,)),
            n0=n0, n1=n1, max_rows=max_rows, cyclic=True).compile()
    assert compiled.memory_analysis() is not None
