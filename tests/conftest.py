"""Test harness config: force an 8-virtual-device CPU platform BEFORE jax
initializes, so sharding/mesh tests run without TPU hardware (SURVEY §7 test
strategy — the reference's analog is multi-process localhost NCCL tests,
test_collective_api_base.py; here a virtual mesh in one process suffices
because collectives are compiler constructs)."""

import os

# Tests run on a virtual 8-device CPU platform whatever the shell's
# JAX_PLATFORMS says (on a TPU host JAX would otherwise take the chip).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Hermetic runs: the engines turn jax's persistent compilation cache on
# (compile_cache.enable), and xdist workers sharing one cache directory
# could read each other's half-written entries. Tests neither read nor
# write it; test_compile_cache_guard checks where enable() points it.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1 runs")
    config.addinivalue_line(
        "markers",
        "faults: fault-injection / resilience test (fast CPU smoke: "
        "tools/ci.sh faults)")

# attach numpy oracles to every registered op (OpTest backbone, SURVEY §4);
# test-only scaffolding, deliberately NOT run on production import
import paddle_tpu  # noqa: E402,F401
from paddle_tpu.ops import oracles as _oracles  # noqa: E402

_oracles.attach_all()


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as pt
    from paddle_tpu.distributed import mesh as mesh_lib
    from paddle_tpu.testing import faults
    pt.seed(1234)
    np.random.seed(1234)
    mesh_lib.set_topology(None)  # no cross-test global-mesh leakage
    yield
    faults.clear()               # no fault-rule leakage across tests


@pytest.fixture
def mesh8():
    import paddle_tpu.distributed as dist
    return dist.init_mesh(dp=2, tp=2, fsdp=2)
