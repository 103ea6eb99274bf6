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


def test_f64c_pair_assembly_compiles_for_v5e(one_chip):
    """The f64c Schur matrices from nonzero-pair lists (gathers, then
    unique-index scatters) at the pds-10 layout: K=11, mb=1,399,
    nb=4,433, link=1,169, with the lists' padded shapes there."""
    from distributedlpsolver_tpu.backends.block_angular import _pair_sum

    K, mb, nb, link = 11, 1399, 4433, 1169
    f64, i32 = jnp.float64, jnp.int32

    def assemble(dBp, mc, mf, mt, gc, gf, gt, lc, lf, lt):
        return (
            _pair_sum(mc, mf, mt, dBp, mb * mb).reshape(K, mb, mb),
            _pair_sum(gc, gf, gt, dBp, link * mb).reshape(K, link, mb),
            _pair_sum(lc, lf, lt, dBp.reshape(1, -1), link * link),
        )

    args = [_spec((K, nb + 1), f64, one_chip)]
    for k, r, T in ((K, 10, 10240), (K, 1, 2560), (1, 12, 1280)):
        args += [_spec((k, r, T), i32, one_chip), _spec((k, r, T), f64, one_chip),
                 _spec((k, T), i32, one_chip)]
    compiled = jax.jit(assemble).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert "scatter" in compiled.as_text()
    # The outputs and one padded copy each: far under the dense
    # assembly's chunk temps (2 GB budget).
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 2e9
