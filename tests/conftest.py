"""Test harness config: force an 8-device virtual CPU mesh.

Multi-chip sharding is validated without TPU hardware by asking XLA for 8
host-platform devices (the TPU analog of multi-node simulation, SURVEY.md §4).
Must run before jax is imported anywhere.
"""

import os

# Force CPU: the ambient session may point JAX_PLATFORMS at a real TPU,
# where default matmul precision would fail parity tolerances. Tier-1 says
# nothing about the chip; chip_smoke.py does.
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA:CPU's default matmul precision downcasts (oneDNN bf16-ish, ~1e-1 abs
# error at d=588) — parity tests need true f32 accumulation.
os.environ["JAX_DEFAULT_MATMUL_PRECISION"] = "highest"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import jax  # noqa: E402

# Belt and braces: a pytest plugin may have half-imported jax before this
# conftest ran, in which case the env vars above were read too late.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

SAMPLE1 = "/root/reference/samples/sample1.npy"


@pytest.fixture(scope="session")
def sample1_events():
    if not os.path.exists(SAMPLE1):
        pytest.skip("reference sample1.npy not available")
    from eventgpt_tpu.ops.raster import load_event_npy

    return load_event_npy(SAMPLE1)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
