"""The per-test time limit of ``tests/conftest.py``, driven from outside:
a child pytest session over a temporary directory in which one test
blocks inside a C call with the interpreter lock held — where no Python
signal handler and no Python timer thread can run — past a 2 s limit."""

import os
import re
import subprocess
import sys
import time

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))

STUCK = '''
import ctypes
import threading

import pytest


def _bystander(release):
    release.wait()


@pytest.mark.time_limit(2)
def test_blocks_in_c():
    release = threading.Event()
    threading.Thread(target=_bystander, args=(release,), daemon=True).start()
    # PyDLL keeps the interpreter lock for the length of the call
    ctypes.PyDLL(None).sleep(600)


def test_passes():
    pass
'''


@pytest.mark.parametrize("xdist", [True, False], ids=["xdist", "one_process"])
def test_a_stuck_test_fails_alone_and_by_name(tmp_path, xdist):
    (tmp_path / "test_stuck.py").write_text(STUCK)
    # the temporary directory has no conftest of its own: load this
    # suite's, guard included, as a plugin
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(filter(None, [
        TESTS, os.path.dirname(TESTS), os.environ.get("PYTHONPATH")])))
    cmd = [sys.executable, "-m", "pytest", "-p", "conftest", "-q",
           "-p", "no:cacheprovider", "-p", "no:randomly", "test_stuck.py"]
    if xdist:
        cmd += ["-p", "xdist", "-n", "2", "--dist", "loadfile"]
    t0 = time.monotonic()
    r = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    secs = time.monotonic() - t0
    out = r.stdout + r.stderr
    # 5 s alone; 18-23 s inside a whole run under six workers, most of it
    # three interpreters importing jax (session, worker, replacement)
    assert secs < 60, (secs, out)
    assert r.returncode == 1, out
    # every thread's stack, the stuck one's and the bystander's
    assert "Timeout (0:00:02)!" in out, out
    assert "in test_blocks_in_c" in out and "in _bystander" in out, out
    if xdist:
        assert "crashed while running 'test_stuck.py::test_blocks_in_c'" \
            in out, out
        assert "1 failed, 1 passed" in out, out


def test_a_run_ends_with_its_test_seconds_and_its_dearest_files(tmp_path):
    """The line the next re-anchor reads the suite's cost from: the sum of
    test-seconds over the files and the dearest files first, with no
    option to ask for it, printed by the controller of an xdist run (the
    driver's), which is handed every worker's reports."""
    for name, secs in (("test_dear.py", 0.6), ("test_cheap.py", 0.0)):
        (tmp_path / name).write_text(
            f"import time\n\n\ndef test_it():\n    time.sleep({secs})\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(filter(None, [
        TESTS, os.path.dirname(TESTS), os.environ.get("PYTHONPATH")])))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "conftest", "-q", "-p",
         "no:cacheprovider", "-p", "no:randomly", "-p", "xdist", "-n", "2",
         "--dist", "loadfile"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    (line,) = re.findall(r"^test-seconds: .*$", r.stdout, re.M)
    said = re.fullmatch(r"test-seconds: (\d+) over 2 files; dearest: "
                        r"test_dear\.py (\d+), test_cheap\.py (\d+)", line)
    assert said, line
    total, dear, cheap = map(int, said.groups())
    assert 1 <= dear <= total <= dear + cheap + 1, line
