"""Test configuration: run JAX on CPU with 8 virtual devices.

This replaces the reference's "4 real VMs + Gloo" test environment
(SURVEY.md §4): the same Mesh/shard_map code paths run unmodified on
8 fake CPU devices, so every distributed strategy is exercised without
TPU hardware.  Must set env vars BEFORE jax is imported anywhere.
"""

import os

# Force CPU even where the environment names another platform (the chip
# machine defaults to TPU): the suite is the CPU-mesh CI by design.  The env
# var covers subprocesses; jax.config below covers an already-imported jax.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", (
    "tests must run on the virtual-device CPU backend")

# Persist XLA compilations (the cache every entry point uses): saves ~4 min of
# repeated CPU-backend compiles across suite runs.  The deviceless TPU AOT
# client cannot DESERIALIZE cache entries (jax warns and recompiles — hence
# the filter); everything else hits.
import warnings  # noqa: E402

warnings.filterwarnings(
    "ignore", message="Error reading persistent compilation cache entry")
from cs744_ddp_tpu.utils.compcache import \
    enable_persistent_compilation_cache  # noqa: E402

enable_persistent_compilation_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from cs744_ddp_tpu.parallel import make_mesh
    assert len(jax.devices()) >= 8, "need 8 virtual devices"
    return make_mesh(8)


@pytest.fixture(scope="session")
def mesh4():
    from cs744_ddp_tpu.parallel import make_mesh
    return make_mesh(4)


@pytest.fixture(scope="session")
def mesh1():
    from cs744_ddp_tpu.parallel import make_mesh
    return make_mesh(1)
