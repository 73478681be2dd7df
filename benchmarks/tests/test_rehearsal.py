"""The rehearsal walks every cell end to end on the CPU and can neither
claim a result nor exit 0; and with no TPU the real command prints no
result line at all."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT
from harness import spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def _run(args, root=ROOT, env=None):
    e = dict(os.environ, **(env or {}))
    e.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py")] + args,
        cwd=root, env=e, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_walks_the_cell(cell, trace):
    p = _run(["--workload", cell, "--rehearse", "--trace", str(trace)])
    assert p.returncode != 0, p.stdout[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    # no number of a CPU run under a metric's name
    assert last["metrics"] and all(m["value"] is None
                                   for m in last["metrics"].values())
    checks = next(json.loads(ln) for ln in p.stdout.splitlines()
                  if ln.startswith('{"note": "checks"'))
    checks.pop("note")
    assert checks and all(v is True for v in checks.values()), checks


def test_no_tpu_no_result_line():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
