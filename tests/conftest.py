"""Test harness config: force an 8-device virtual CPU mesh so all distributed
tests run without TPU hardware (reference pattern: test/custom_runtime/ fake
custom_cpu plugin — test a backend without the hardware; here the PJRT CPU
client plays that role).

Must run before jax is imported: the tests ask for the CPU by name
(`JAX_PLATFORMS=cpu`), which is also what lets `init_platform()` — called
by the tools and entry points some tests drive — run the Pallas kernels
in interpret mode instead of refusing a machine with no TPU.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")  # in case a plugin imported jax

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as paddle
    paddle.seed(2024)
    yield
