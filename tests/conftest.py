"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference's distributed tests require >= 8 real GPUs under torchrun
(``tests/test_utilities.py:6-30`` — real NCCL, no simulation).  We do
better (as SURVEY.md §4 prescribes): XLA's host platform is forced to
expose 8 virtual CPU devices, so every TP/PP/DP/SP test runs in CI with no
hardware.
"""

import os

# Must happen before jax initializes its backends.  The collective-call
# rendezvous timeouts default to 20s/40s; on a loaded or few-core CI box
# the 8 virtual device threads can legitimately take longer to converge
# (compilation runs on the same cores), and the default *aborts the
# process*.  Raise them — slow is fine, SIGABRT mid-suite is not.
_flags = os.environ.get("XLA_FLAGS", "")
for _f in (
    "--xla_force_host_platform_device_count=8",
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=300",
    "--xla_cpu_collective_call_terminate_timeout_seconds=7200",
):
    if _f.lstrip("-").split("=")[0] not in _flags:
        _flags = (_flags + " " + _f).strip()
os.environ["XLA_FLAGS"] = _flags
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import pytest  # noqa: E402

from megatron_llm_tpu import topology  # noqa: E402


def pytest_configure(config):
    # tier-1 CI runs `-m 'not slow'` (ROADMAP.md); slow = multi-process /
    # subprocess-spawning suites (router failover, replica fleets)
    config.addinivalue_line(
        "markers", "slow: long multi-process tests excluded from tier-1")
    config.addinivalue_line(
        "markers", "chaos: serving fault-injection tests (fast chaos "
                   "units run in tier-1; the multi-process fleet e2e is "
                   "additionally marked slow)")


class Utils:
    """Analogue of the reference's tests/test_utilities.py Utils."""

    world_size = 8

    @staticmethod
    def initialize_model_parallel(tp=1, pp=1, vpp=None, cp=1, num_slices=1):
        topology.destroy_model_parallel()
        return topology.initialize_model_parallel(
            tp, pp, vpp, context_parallel_size=cp, num_slices=num_slices)

    @staticmethod
    def destroy_model_parallel():
        topology.destroy_model_parallel()


@pytest.fixture
def utils():
    yield Utils
    Utils.destroy_model_parallel()


@pytest.fixture(autouse=True)
def _reset_topology():
    yield
    topology.destroy_model_parallel()


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled executables + tracing caches between test modules.

    A full-suite run accumulates hundreds of compiled shard_map programs;
    on a small CI box the later heavyweight modules (test_pipeline's 1F1B
    engines) then slow to the point of tripping XLA's collective-call
    terminate timeout — a SIGABRT, not a failure.  Per-module cache
    clearing keeps each module's footprint what it is when run alone."""
    yield
    jax.clear_caches()
