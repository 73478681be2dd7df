"""Model-health observatory (megatron_llm_tpu/health.py): per-group
grad/param/update norms vs a hand-computed NumPy reference, offender
diagnosis, the derived --log_params_norm partition, zero recompiles after
warmup with stats enabled (mixed with eval), nan@k localization naming
the poisoned group in the rewind log + flight-recorder dump,
pipeline-parallel stats parity with the single-program path, and the
tools/health_report.py summarizer."""

import argparse
import json
import math
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from megatron_llm_tpu import global_vars, health, telemetry
from megatron_llm_tpu.config import ParallelConfig, TrainConfig
from megatron_llm_tpu.global_vars import get_counters
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.optimizer import MegatronOptimizer
from megatron_llm_tpu.optimizer.optimizer import global_grad_norm
from megatron_llm_tpu.parallel import sharding as sh
from megatron_llm_tpu.parallel.pipeline import (
    build_pipeline_grad_fn,
    build_pipeline_train_step,
)
from megatron_llm_tpu.resilience import (
    FaultInjector,
    ResilienceConfig,
    ResilienceManager,
    recovery_counters,
)
from megatron_llm_tpu.telemetry import (
    TELEMETRY_SCHEMA_VERSION,
    build_telemetry,
)
from megatron_llm_tpu.training import pretrain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry_state():
    global_vars.reset_counters()
    telemetry.install_stream(None)
    yield
    telemetry.install_stream(None)
    global_vars.reset_counters()


def _setup(utils):
    cfg = llama_config("tiny", seq_length=16, max_position_embeddings=16,
                       padded_vocab_size=64, num_layers=2, hidden_size=32,
                       num_attention_heads=4, ffn_hidden_size=64)
    model = LlamaModel(cfg)
    utils.initialize_model_parallel(tp=1)
    params = model.init(jax.random.PRNGKey(0))
    params = sh.shard_params(params, model.param_specs(params))

    def it():
        rng = np.random.RandomState(0)
        while True:
            toks = jnp.asarray(rng.randint(0, 64, size=(1, 8, 16)))
            yield {
                "tokens": toks,
                "labels": jnp.roll(toks, -1, axis=-1),
                "loss_mask": jnp.ones_like(toks, jnp.float32),
            }

    return model, params, it


def _tc(iters):
    return TrainConfig(micro_batch_size=8, global_batch_size=8,
                       train_iters=iters, lr=1e-2, optimizer="adam", seed=3)


def _telemetry_args(**kw):
    """A parsed-args stand-in with the telemetry group's fields."""
    base = dict(structured_log_dir=None, flight_recorder_size=64,
                profile=False, profile_step_start=2, profile_step_end=3,
                profile_dir=None, profiler_port=None, trace_dir=None,
                trace_buffer_size=100_000, straggler_threshold=1.5)
    base.update(kw)
    return argparse.Namespace(**base)


# ---------------------------------------------------------------------------
# Grouping + on-device stats vs a NumPy reference
# ---------------------------------------------------------------------------

def test_layer_group_names_synthetic_and_model():
    # synthetic tree with the canonical top-level layout
    tree = {
        "embedding": {"w": jnp.zeros((4, 5))},
        "lm_head": {"w": jnp.zeros((5, 4))},
        "transformer": {
            "final_norm": {"scale": jnp.zeros((5,))},
            "layers": {"w": jnp.zeros((3, 5, 5))},
        },
    }
    assert health.layer_group_names(tree) == [
        "embedding", "layer_000", "layer_001", "layer_002",
        "lm_head", "final_norm"]

    # a real model's param tree: embedding first, one group per layer row
    cfg = llama_config("tiny", seq_length=16, max_position_embeddings=16,
                       padded_vocab_size=64, num_layers=2, hidden_size=32,
                       num_attention_heads=4, ffn_hidden_size=64)
    params = LlamaModel(cfg).init(jax.random.PRNGKey(0))
    names = health.layer_group_names(params)
    assert names[:3] == ["embedding", "layer_000", "layer_001"]
    assert "final_norm" in names
    assert len(names) == len(set(names))


def test_compute_layer_stats_matches_numpy():
    rng = np.random.RandomState(7)

    def tree(scale=1.0):
        return {
            "embedding": {"w": rng.randn(4, 5).astype(np.float32) * scale},
            "transformer": {
                "final_norm": {"s": rng.randn(5).astype(np.float32) * scale},
                "layers": {
                    "a": rng.randn(3, 2, 5).astype(np.float32) * scale,
                    "b": rng.randn(3, 4).astype(np.float32) * scale,
                },
            },
        }

    params, grads, updates = tree(), tree(0.1), tree(0.01)
    grads["embedding"]["w"][0, 0] = np.inf       # 1 bad entry in embedding
    grads["transformer"]["layers"]["a"][1, 0, :2] = np.nan   # 2 in layer_001

    names = health.layer_group_names(params)
    assert names == ["embedding", "layer_000", "layer_001", "layer_002",
                     "final_norm"]
    stats = jax.jit(health.compute_layer_stats)(
        jax.tree_util.tree_map(jnp.asarray, params),
        jax.tree_util.tree_map(jnp.asarray, grads),
        jax.tree_util.tree_map(jnp.asarray, updates))

    def ref_norm(t, group):
        if group == "embedding":
            arrs = [t["embedding"]["w"]]
        elif group == "final_norm":
            arrs = [t["transformer"]["final_norm"]["s"]]
        else:
            i = int(group.split("_")[1])
            arrs = [t["transformer"]["layers"]["a"][i],
                    t["transformer"]["layers"]["b"][i]]
        return math.sqrt(sum(float(np.sum(np.square(a.astype(np.float64))))
                             for a in arrs))

    for i, g in enumerate(names):
        np.testing.assert_allclose(float(stats["param_norm"][i]),
                                   ref_norm(params, g), rtol=1e-5,
                                   err_msg=f"param_norm[{g}]")
        np.testing.assert_allclose(float(stats["update_norm"][i]),
                                   ref_norm(updates, g), rtol=1e-5,
                                   err_msg=f"update_norm[{g}]")
    # grad norms: poisoned groups go non-finite, the rest match the ref
    assert not math.isfinite(float(stats["grad_norm"][0]))    # embedding
    assert math.isnan(float(stats["grad_norm"][2]))           # layer_001
    for i in (1, 3, 4):
        np.testing.assert_allclose(float(stats["grad_norm"][i]),
                                   ref_norm(grads, names[i]), rtol=1e-5,
                                   err_msg=f"grad_norm[{names[i]}]")
    assert [int(v) for v in stats["nonfinite_grads"]] == [1, 0, 2, 0, 0]


def test_record_encoding_and_offender_diagnosis():
    names = ["embedding", "layer_000", "layer_001", "lm_head"]
    stats = {
        "grad_norm": np.array([1.0, 1.0, np.nan, 100.0]),
        "param_norm": np.array([10.0, 10.0, 10.0, 0.0]),
        "update_norm": np.array([0.01, 0.02, np.inf, 0.5]),
        "nonfinite_grads": np.array([0, 0, 3, 0]),
    }
    rec = health.to_record(names, stats)
    assert rec["groups"] == names
    assert rec["grad_norm"][2] == "nan" and rec["update_norm"][2] == "inf"
    json.dumps(rec)    # plain JSON despite the non-finites
    assert rec["update_ratio"][0] == pytest.approx(1e-3)
    assert rec["update_ratio"][2] is None      # non-finite update norm
    assert rec["update_ratio"][3] is None      # zero param norm
    assert math.isnan(health.record_value("nan"))
    assert health.record_value("-inf") == -math.inf
    assert health.record_value(2.5) == 2.5
    assert health.derived_params_norm(rec) == pytest.approx(
        math.sqrt(3 * 10.0 ** 2))

    off = health.find_offenders(rec)
    assert off["first_nonfinite"] == "layer_001"
    assert off["nonfinite"] == ["layer_001"]
    assert [o["group"] for o in off["outliers"]] == ["lm_head"]
    assert off["outliers"][0]["ratio_to_median"] == pytest.approx(100.0)
    desc = health.describe_offenders(off)
    assert "layer_001" in desc and "lm_head" in desc
    # a clean record diagnoses nothing
    clean = health.to_record(names, {
        "grad_norm": np.ones(4), "param_norm": np.ones(4),
        "nonfinite_grads": np.zeros(4, np.int32)})
    assert health.describe_offenders(health.find_offenders(clean)) is None


def test_derived_params_norm_partitions_global_norm():
    cfg = llama_config("tiny", seq_length=16, max_position_embeddings=16,
                       padded_vocab_size=64, num_layers=2, hidden_size=32,
                       num_attention_heads=4, ffn_hidden_size=64)
    params = LlamaModel(cfg).init(jax.random.PRNGKey(1))
    names = health.layer_group_names(params)
    stats = jax.jit(health.compute_layer_stats)(params, params)
    rec = health.to_record(names, jax.device_get(stats))
    assert health.derived_params_norm(rec) == pytest.approx(
        float(global_grad_norm(params)), rel=1e-5)


# ---------------------------------------------------------------------------
# In-loop: zero recompiles, JSONL schema, nan@k localization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("traced", [True, False])
def test_pretrain_layer_stats_zero_recompiles(utils, tmp_path, traced):
    """The acceptance run: stats on (interval 2), --log_params_norm
    derived from the partition, eval mixed in — after warmup the step
    never recompiles, and the JSONL stream carries the per-group record
    exactly at stats boundaries.  Compiles are heard, and ``recompiles``
    is in every log record, with ``--trace_dir`` or without it."""
    model, params, it = _setup(utils)
    d = str(tmp_path)
    tel = build_telemetry(
        _telemetry_args(structured_log_dir=d,
                        trace_dir=d if traced else None), model)
    assert (tel.tracing is not None) == traced
    seen = {}
    try:
        pretrain(model, params, _tc(6), ParallelConfig(), it(),
                 log_interval=1, log_layer_stats_interval=2,
                 log_params_norm=True, telemetry=tel,
                 eval_iterator=it(), eval_interval=3, eval_iters=2,
                 on_metrics=lambda i, m: seen.setdefault(i, m))
    finally:
        tel.close()
    assert int(get_counters().get("recompiles", 0)) == 0

    records = [r for r in map(json.loads,
                              open(os.path.join(d, "telemetry.jsonl")))
               if r["kind"] == "log"]
    assert [r["iteration"] for r in records] == [1, 2, 3, 4, 5, 6]
    for r in records:
        assert r["schema"] == TELEMETRY_SCHEMA_VERSION
        assert r["recompiles"] == 0
        ls = r.get("layer_stats")
        assert (ls is not None) == (r["iteration"] % 2 == 0)
        if ls is None:
            continue
        G = len(ls["groups"])
        assert ls["groups"][:3] == ["embedding", "layer_000", "layer_001"]
        for key in ("grad_norm", "param_norm", "update_norm",
                    "update_ratio", "nonfinite_grads"):
            assert len(ls[key]) == G
        assert all(n == 0 for n in ls["nonfinite_grads"])
        assert all(health.record_value(v) > 0 for v in ls["param_norm"])
        # the LR schedule decays to 0 at the final iteration, so the last
        # boundary's update ratios are legitimately 0.0
        assert all(r is None or r >= 0 for r in ls["update_ratio"])
    # --log_params_norm was served every boundary (derived, no extra jit)
    for i, m in seen.items():
        pn = float(m["params norm"])
        assert math.isfinite(pn) and pn > 0


def test_nan_injection_names_offending_layer(utils, tmp_path, capsys):
    """nan@3 poisons every group's grads (via the loss mask): the bad
    check announces suspect layers, the rewind message names them, and
    the flight-recorder dump carries the health record + diagnosis."""
    model, params, it = _setup(utils)
    d = str(tmp_path)
    tel = build_telemetry(_telemetry_args(structured_log_dir=d), model)
    rm = ResilienceManager(
        ResilienceConfig(snapshot_interval=1, patience=1, spike_factor=0),
        injector=FaultInjector.from_spec("nan@3"))
    try:
        pretrain(model, params, _tc(6), ParallelConfig(), it(),
                 log_interval=1, log_layer_stats_interval=1,
                 telemetry=tel, resilience=rm)
    finally:
        rm.close()
        tel.close()
    assert recovery_counters()["rewinds"] == 1
    out = capsys.readouterr().out
    assert "suspect layers at iteration 3" in out
    assert "first: embedding" in out
    assert "suspect layers:" in out    # the rewind line repeats the blame

    dump = os.path.join(d, "flight_recorder.json")
    assert os.path.exists(dump)
    payload = json.loads(open(dump).read())
    assert payload["reason"].startswith("rewind #1")
    assert "embedding" in payload["reason"]
    healths = [r for r in payload["records"] if r.get("kind") == "health"]
    assert healths and healths[-1]["iteration"] == 3
    assert healths[-1]["offenders"]["first_nonfinite"] == "embedding"
    assert healths[-1]["layer_stats"]["groups"][0] == "embedding"


# ---------------------------------------------------------------------------
# Pipeline-parallel parity
# ---------------------------------------------------------------------------

def test_pipeline_layer_stats_parity(utils):
    """Per-group stats computed on the pipeline grad fn's gradients match
    the single-program reference, and the pipelined train step emits the
    same fixed-shape stats pytree as build_train_step."""
    cfg = llama_config("tiny", num_layers=4, seq_length=32,
                       max_position_embeddings=32, padded_vocab_size=128)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 128, (2, 2, 32)))
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1),
             "loss_mask": jnp.ones((2, 2, 32), jnp.float32)}

    def unpiped_loss(p):
        tot, den = 0.0, 0.0
        for i in range(2):
            lt = model(p, batch["tokens"][i], labels=batch["labels"][i],
                       train=False)
            tot, den = tot + lt.sum(), den + lt.size
        return tot / den

    g_base = jax.grad(unpiped_loss)(params)
    names = health.layer_group_names(params)
    ref = jax.device_get(jax.jit(health.compute_layer_stats)(params, g_base))

    utils.initialize_model_parallel(tp=1, pp=2)
    ps = sh.shard_params(params, model.param_specs(params))
    grad_fn = build_pipeline_grad_fn(model, 2, 2)
    _, g_pipe = jax.jit(lambda p, b, k: grad_fn(p, b, k, train=False))(
        ps, batch, jax.random.PRNGKey(0))
    got = jax.device_get(jax.jit(health.compute_layer_stats)(ps, g_pipe))
    assert names[:5] == ["embedding", "layer_000", "layer_001",
                         "layer_002", "layer_003"]
    np.testing.assert_allclose(got["grad_norm"], ref["grad_norm"],
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(got["param_norm"], ref["param_norm"],
                               rtol=1e-5)
    assert [int(v) for v in got["nonfinite_grads"]] == [0] * len(names)

    # the pipelined train step surfaces the same pytree shape
    tc = TrainConfig(micro_batch_size=2, global_batch_size=4, lr=1e-3)
    pc = ParallelConfig(pipeline_model_parallel_size=2,
                        data_parallel_size=4)
    opt = MegatronOptimizer(tc)
    opt_state = opt.init(ps)
    step = build_pipeline_train_step(model, opt, pc, 2, layer_stats=True)
    _, _, m = step(ps, opt_state, batch, jax.random.PRNGKey(0), 1e-3, 0.0)
    ls = jax.device_get(m["layer_stats"])
    for key in ("grad_norm", "param_norm", "update_norm",
                "nonfinite_grads"):
        assert ls[key].shape == (len(names),)
    rec = health.to_record(names, ls)
    assert health.derived_params_norm(rec) > 0
    assert all(n == 0 for n in rec["nonfinite_grads"])


# ---------------------------------------------------------------------------
# tools/health_report.py + telemetry_report layer-stats aggregates
# ---------------------------------------------------------------------------

def _synthetic_stream(path):
    groups = ["embedding", "layer_000", "layer_001", "lm_head"]

    def rec(it, **ls):
        return {"schema": 3, "kind": "log", "iteration": it,
                "lm_loss": 2.0, "step_time_secs": 0.01,
                "layer_stats": {"groups": groups, **ls}}

    records = [
        # schema-2-era record (no layer_stats) parses alongside
        {"schema": 2, "kind": "log", "iteration": 5, "lm_loss": 2.1,
         "step_time_secs": 0.01},
        {"kind": "dispatch", "iteration": 9},    # non-log records skipped
        rec(10, grad_norm=[1.0, 1.1, 0.9, 1.05],
            param_norm=[10.0, 10.0, 10.0, 10.0],
            update_norm=[0.01, 0.01, 0.01, 0.01],
            update_ratio=[1e-3, 1e-3, 1e-3, 1e-3],
            nonfinite_grads=[0, 0, 0, 0]),
        rec(20, grad_norm=[1.0, 50.0, "nan", 1.0],
            param_norm=[10.0, 10.0, 10.0, 10.0],
            update_norm=[0.5, 0.01, "inf", 0.01],
            update_ratio=[0.05, 1e-3, None, 1e-3],
            nonfinite_grads=[0, 0, 4, 0]),
    ]
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
        f.write("{truncated\n")    # crash-torn final line is tolerated


def test_health_report_cli(tmp_path):
    stream = tmp_path / "telemetry.jsonl"
    _synthetic_stream(stream)

    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "health_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "layer-stats boundaries: 2" in r.stdout
    assert "NONFINITE" in r.stdout
    assert "GRAD>4xMED" in r.stdout
    assert "UPD-RATIO" in r.stdout
    assert "iteration 20: layer_001 (first: layer_001)" in r.stdout

    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "health_report.py"),
         str(stream), "--json"],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["nan_events"] == [{"iteration": 20, "groups": ["layer_001"]}]
    by_group = {e["group"]: e for e in doc["table"]}
    assert by_group["layer_001"]["flags"] == ["NONFINITE"]
    assert "GRAD>4xMED" in by_group["layer_000"]["flags"]
    assert "UPD-RATIO" in by_group["embedding"]["flags"]
    assert by_group["lm_head"]["flags"] == []
    assert by_group["embedding"]["update_ratio_median"] == pytest.approx(
        0.5 * (1e-3 + 0.05))

    # --last trims to the newest boundaries
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "health_report.py"),
         str(stream), "--json", "--last", "1"],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert json.loads(r.stdout)["boundaries"] == 1

    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "health_report.py"),
         str(tmp_path / "missing.jsonl")],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert r2.returncode == 2

    # a stream with no layer_stats records exits 2 with a pointer
    bare = tmp_path / "bare.jsonl"
    bare.write_text(json.dumps({"kind": "log", "iteration": 1}) + "\n")
    r3 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "health_report.py"),
         str(bare)],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert r3.returncode == 2
    assert "log_layer_stats_interval" in r3.stderr


def test_telemetry_report_layer_stats_aggregates(tmp_path):
    stream = tmp_path / "telemetry.jsonl"
    _synthetic_stream(stream)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         str(stream)],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "worst update ratio 0.05" in r.stdout
    assert "NaN-layer events: 1" in r.stdout

    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         str(stream), "--json"],
        capture_output=True, text=True, timeout=60, cwd=ROOT)
    agg = json.loads(r.stdout)["aggregates"]
    assert agg["worst_update_ratio"] == pytest.approx(0.05)
    assert agg["nan_layer_events"] == 1
