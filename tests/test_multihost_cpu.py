"""REAL multi-host integration: two OS processes with one CPU device
each, rendezvoused by jax.distributed over localhost (gloo collectives)
through the torchrun-style env contract — upgrading the multi-host
evidence from single-process fakes to an actual 2-process run of
initialize_distributed -> mesh -> place_host_batch -> dp=2 train step
-> cross-host checksum (incl. a real divergence catch)."""

import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time

import pytest

REPO =os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_multihost_worker.py")
MS_WORKER = os.path.join(REPO, "tests", "_multislice_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _two_process_env():
    base = dict(os.environ)
    base["JAX_PLATFORMS"] = "cpu"
    # one device per process: drop the 8-virtual-device conftest flags
    base["XLA_FLAGS"] = " ".join(
        f for f in base.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f)
    base.update(MASTER_ADDR="127.0.0.1", MASTER_PORT=str(_free_port()),
                WORLD_SIZE="2")
    return base


@pytest.mark.slow
def test_two_process_dp_train_step():
    base = _two_process_env()

    procs = []
    for rank in range(2):
        env = dict(base, RANK=str(rank))
        procs.append(subprocess.Popen(
            [sys.executable, WORKER], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))

    for rank, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"rank {rank} rc={rc}\n{err[-3000:]}"
        assert f"RANK{rank} CHECKSUM_OK" in out
        assert f"RANK{rank} DIVERGENCE_CAUGHT" in out

    # data-parallel consistency: both processes computed the same loss
    losses = [re.search(r"LOSS ([0-9.]+)", out).group(1)
              for _, out, _ in outs]
    assert losses[0] == losses[1], losses


@pytest.mark.slow
def test_two_process_slice_axis_hierarchical_reduce():
    """The ``slice`` mesh axis spans the process boundary (each process
    is one slice), so the second hop of the hierarchical all-reduce
    crosses a real process link — and must stay checksum-identical to
    the flat psum, with train-step loss parity between the two paths."""
    base = _two_process_env()

    procs = []
    for rank in range(2):
        env = dict(base, RANK=str(rank), MULTISLICE_MODE="step")
        procs.append(subprocess.Popen(
            [sys.executable, MS_WORKER], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))

    for rank, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"rank {rank} rc={rc}\n{err[-3000:]}"
        assert f"RANK{rank} HIERARCHICAL_ALLREDUCE_OK" in out
        assert f"RANK{rank} HIER_FLAT_PARITY_OK" in out

    losses = [re.search(r"LOSS ([0-9.]+)", out).group(1)
              for _, out, _ in outs]
    assert losses[0] == losses[1], losses


@pytest.mark.slow
def test_two_process_preemption_rescue():
    """SIGTERM delivered to ONE slice mid-run: boundary consensus makes
    BOTH processes save the rescue checkpoint and exit with code 17, and
    the checkpoint (plus run_shape.json) is loadable afterwards."""
    base = _two_process_env()
    save_dir = tempfile.mkdtemp()

    procs = []
    for rank in range(2):
        env = dict(base, RANK=str(rank), MULTISLICE_MODE="preempt",
                   MULTISLICE_SAVE_DIR=save_dir)
        procs.append(subprocess.Popen(
            [sys.executable, MS_WORKER], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    # watch rank 0's output for training progress, then preempt ONLY it
    lines = []
    deadline = time.monotonic() + 300
    try:
        for line in procs[0].stdout:
            lines.append(line)
            if re.search(r"RANK0 STEP [3-9]", line):
                procs[0].send_signal(signal.SIGTERM)
                break
            if time.monotonic() > deadline:
                raise TimeoutError("no training progress:\n"
                                   + "".join(lines)[-3000:])
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append((p.returncode, "".join(lines) + out
                         if p is procs[0] else out))
    except Exception:
        for q in procs:
            q.kill()
        raise

    # the whole fleet honored the consensus: rescue save + exit 17
    for rank, (rc, out) in enumerate(outs):
        assert rc == 17, f"rank {rank} rc={rc}\n{out[-3000:]}"
        assert "exiting on termination signal" in out, out[-3000:]

    # rescue checkpoint is loadable (and records the fleet shape)
    from megatron_llm_tpu import checkpointing, multislice
    it, release = checkpointing.read_tracker(save_dir)
    assert it and it >= 1 and not release
    params, _, meta = checkpointing.load_checkpoint(save_dir)
    assert meta["iteration"] == it
    assert params is not None
    shape = multislice.read_run_shape(save_dir)
    assert shape is not None
    assert shape["num_slices"] == 2 and shape["processes"] == 2
