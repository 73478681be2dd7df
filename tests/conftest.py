"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference's distributed tests require >= 8 real GPUs under torchrun
(``tests/test_utilities.py:6-30`` — real NCCL, no simulation).  We do
better (as SURVEY.md §4 prescribes): XLA's host platform is forced to
expose 8 virtual CPU devices, so every TP/PP/DP/SP test runs in CI with no
hardware.
"""

import collections
import faulthandler
import os

# Every test gets this long for its setup, call and teardown together;
# `@pytest.mark.time_limit(seconds)` gives a named test another limit.
# pytest-timeout is not in the installation, and a main thread stuck
# inside an XLA call never returns to run a Python signal handler or to
# release the interpreter lock to a Python timer, so the limit is the
# fault handler's own C thread: at expiry it writes every thread's stack
# to the real stderr and ends the process with _exit(1).  Under xdist
# that is one crashed worker: the test running in it fails by name, the
# worker is replaced, and the rest of its file runs in the new one.  In
# a one-process run the stacks (the test's file, line and function among
# them) are the last thing printed and the run ends there.
TIME_LIMIT_SECS = 300

# Must happen before jax initializes its backends.  The collective-call
# rendezvous timeouts default to 20s/40s; on a loaded or few-core CI box
# the 8 virtual device threads can legitimately take longer to converge
# (compilation runs on the same cores), and the default *aborts the
# process*.  Raise them, but keep the abort under TIME_LIMIT_SECS: XLA
# then ends a stuck rendezvous first, and names it and the participants
# that never arrived.
_flags = os.environ.get("XLA_FLAGS", "")
for _f in (
    "--xla_force_host_platform_device_count=8",
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=60",
    "--xla_cpu_collective_call_terminate_timeout_seconds=240",
):
    if _f.lstrip("-").split("=")[0] not in _flags:
        _flags = (_flags + " " + _f).strip()
os.environ["XLA_FLAGS"] = _flags
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA's CPU client runs each in-flight collective of each virtual device
# on a thread of one pool, where it blocks until its peers arrive, and
# sizes that pool by the box's cores, never under the device count: 8
# here.  A pp 2 x tp 2 program keeps two or three collectives in flight
# on a device, so eight waiters (two in one permute, four in another, two
# in tp all-reduces) can hold every thread while the participants they
# wait for have none to arrive on: test_pipeline.py's
# test_manual_1f1b_matches_unpipelined[2-2] hung so about one run in
# three.  PJRT_NPROC is the client's own knob for the pool's size.
os.environ["PJRT_NPROC"] = "32"

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
    config.addinivalue_line(
        "markers", "time_limit(seconds): this test's own limit in place "
                   "of TIME_LIMIT_SECS, which every other test has")
    # output capture is suspended while plugins are configured, so this
    # is the terminal (or the driver's log), not a capture file
    fd = config.stash[_REAL_STDERR] = os.dup(2)
    config.add_cleanup(lambda: os.close(fd))


_REAL_STDERR = pytest.StashKey[int]()

# test-seconds by file (set-up, call and teardown, as the junit XML counts
# them), in the process that reports: under xdist the controller, which
# is handed every worker's reports
_SECONDS = collections.Counter()


def pytest_runtest_logreport(report):
    _SECONDS[report.nodeid.split("::")[0]] += report.duration


def pytest_terminal_summary(terminalreporter):
    """What the run cost, in one line of its log: the sum of test-seconds
    and the five dearest files (``--dist loadfile`` puts a file on one
    worker, so a file is also the unit of balance).  ROADMAP.md's D13
    holds the suite to its time limit by this line."""
    if _SECONDS:
        dearest = ", ".join(f"{name} {secs:.0f}"
                            for name, secs in _SECONDS.most_common(5))
        terminalreporter.write_line(
            f"test-seconds: {sum(_SECONDS.values()):.0f} over "
            f"{len(_SECONDS)} files; dearest: {dearest}")


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item):
    marker = item.get_closest_marker("time_limit")
    limit = marker.args[0] if marker else TIME_LIMIT_SECS
    faulthandler.dump_traceback_later(
        limit, exit=True, file=item.config.stash[_REAL_STDERR])
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """xdist 3.8.0's file-wise scheduler puts a crashed worker's files
    back in the queue whole, the test that ended the worker included
    (``LoadScopeScheduling.remove_node``), so a test that overruns every
    time would end one replacement after another until xdist gives up
    with the rest of the suite unrun.  That test has failed: count it
    done, and hand on only what follows it."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class CrashedTestRunsOnce(LoadFileScheduling):
        def remove_node(self, node):
            workload = self.assigned_work.pop(node)
            crashitem = None
            for scope, unit in workload.items():
                pending = [n for n, done in unit.items() if not done]
                if pending and crashitem is None:
                    crashitem = pending.pop(0)
                    unit[crashitem] = True
                if pending:
                    self.workqueue[scope] = unit
            if crashitem is not None:
                for other in self.assigned_work:
                    self._reschedule(other)
            return crashitem

    return CrashedTestRunsOnce(config, log)


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


@pytest.fixture(scope="module")
def kept_engines():
    """This module's engines of the served families' tiny models
    (``tests/_family.py::Engines``): an engine of a shape is built once a
    module, not once a test."""
    import _family

    kept = _family.Engines()
    yield kept
    kept.drop()


@pytest.fixture
def engines(kept_engines, monkeypatch):
    """The module's engines for one test: a tap or interpret mode laid
    over one is undone when the test ends."""
    return kept_engines.during(monkeypatch)
