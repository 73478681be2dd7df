"""chip_smoke.py and the start-up rules it rests on.

What needs the chip is proved on the chip (CHANGES.md records the run).
Here: the tiny CPU rehearsal walks both phases through the real entry
points and can never pass; a run that finds no chip prints no result;
the compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says or to the
checkout; no entry point falls back to the CPU by itself; an unknown
device kind has no peak.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from megatron_llm_tpu import initialize
from megatron_llm_tpu.telemetry import ThroughputCalculator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*argv, **env):
    base = dict(os.environ, **env)
    # the session's 8 virtual devices are not the smoke's business
    base.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                           *argv], env=base, cwd=ROOT, capture_output=True,
                          text=True, timeout=600)


@pytest.fixture(scope="module")
def rehearsal():
    proc = _smoke("--rehearse")
    records = [json.loads(ln) for ln in proc.stdout.splitlines()
               if ln.startswith("{")]
    return proc, records


def test_rehearsal_runs_both_phases(rehearsal):
    proc, records = rehearsal
    phases = {r["phase"]: r for r in records if "phase" in r}
    assert set(phases) == {"train", "serve"}, proc.stderr[-3000:]
    for r in phases.values():
        assert r["passed"] and not r["failed"], r["failed"]
        assert r["device"]["platform"] == "cpu" and r["rehearsal"]
    train, serve = phases["train"], phases["serve"]
    assert len(train["losses"]) == 5
    # what only the chip can show is marked not run, never passed
    assert train["checks"]["mosaic_in_train_step"] is None
    assert serve["checks"]["mosaic_in_decode"] is None
    assert serve["paged_kernel"] == serve["prefill_kernel"] == "pallas"
    assert len(serve["requests"]) == 5
    assert serve["recompiles_after_warmup"] == 0


def test_rehearsal_can_never_pass(rehearsal):
    proc, records = rehearsal
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert records[-1]["ok"] is False and records[-1]["phases_passed"]
    # a time from a CPU run is never written under a device metric's name
    for r in records:
        assert not {"compile_secs", "steady_step_secs", "startup_secs",
                    "peak_bytes_in_use"} & set(r)


def test_no_chip_prints_no_result():
    proc = _smoke(JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.fixture
def cache_config():
    """The two settings enable_compile_cache may touch, put back after."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_the_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    initialize.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(ROOT, ".jax_cache")


def test_compile_cache_dir_from_outside_is_left_alone(cache_config,
                                                      monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "set-by-jax-from-env")
    initialize.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == "set-by-jax-from-env"


def test_compile_cache_dir_is_set_in_one_place():
    hits = subprocess.run(
        ["grep", "-rln", "--include=*.py", "jax_compilation_cache_dir",
         "megatron_llm_tpu", "tools", "tasks", "chip_smoke.py",
         "finetune.py", "__graft_entry__.py"],
        cwd=ROOT, capture_output=True, text=True).stdout.split()
    assert hits == ["megatron_llm_tpu/initialize.py"]


def test_cpu_runs_only_where_it_was_asked_for():
    # the session runs under JAX_PLATFORMS=cpu: asked for, so allowed
    assert initialize.select_platform("tpu") == "cpu"
    # the same CPU backend with nothing asked is JAX having fallen back
    # by itself (no chip): refused
    was = jax.config.jax_platforms
    jax.config.update("jax_platforms", "")
    try:
        with pytest.raises(SystemExit, match="no TPU found"):
            initialize.select_platform("tpu")
        assert initialize.select_platform("cpu") == "cpu"   # --device=cpu
    finally:
        jax.config.update("jax_platforms", was)


def test_entry_point_refuses_a_silent_cpu_fallback():
    """finetune.py on a machine with no chip and no request for the CPU:
    a message and a non-zero exit, not a training run on the CPU."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "finetune.py"),
         "--model_name=llama2", "--num_layers=1", "--hidden_size=32",
         "--num_attention_heads=2", "--seq_length=16",
         "--micro_batch_size=1", "--train_iters=1", "--vocab_size=64"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert "iteration" not in proc.stdout


def test_unknown_device_kind_has_no_peak(monkeypatch):
    class Dev:
        device_kind = "TPU v9 mega"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
    with pytest.raises(ValueError, match="TPU v9 mega"):
        ThroughputCalculator.from_model(object())
