"""Test configuration.

Tests run on CPU with 8 virtual XLA host devices so the mesh/psum sharded
code paths execute without TPU hardware (SURVEY.md §4: the analogue of the
reference's `mpirun -np N` single-machine multi-rank testing). Must run
before jax initializes, hence module level in conftest.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# JAX reads JAX_PLATFORMS when it is imported; pin the config as well in
# case something imported jax before this module set the variable.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
