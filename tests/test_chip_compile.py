"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The TPU compiler is installed here and compiles for a described chip
that is not attached (on-chip-measurement guide §2.3): Mosaic refusals,
VMEM overruns and programs that do not fit the device surface here, at
no chip time. Nothing runs, so these tests say nothing about results or
speed. The topology is described inside a module-scoped fixture — never
at import — so every xdist worker collects the same tests and only the
worker given this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from distributedlpsolver_tpu.ops.chol_mxu import chol_mxu_factor
from distributedlpsolver_tpu.ops.normal_eq import (
    BLOCK_K,
    BLOCK_M,
    _round_up,
    normal_eq_pallas,
)

_V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around them.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,n", [(2048, 10240), (10000, 50000)])
def test_normal_eq_pallas_compiles_for_v5e(one_chip, m, n):
    mp, np_ = _round_up(m, BLOCK_M), _round_up(n, BLOCK_K)
    A = _spec((mp, np_), jnp.float32, one_chip)
    d = _spec((n,), jnp.float32, one_chip)
    compiled = normal_eq_pallas.lower(A, d, out_m=m).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < _V5E_HBM


def test_chol_mxu_factor_compiles_for_v5e_f64(one_chip):
    M = _spec((2048, 2048), jnp.float64, one_chip)
    compiled = chol_mxu_factor.lower(M).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < _V5E_HBM
