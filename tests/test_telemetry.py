"""Unified telemetry (megatron_llm_tpu/telemetry.py): MFU arithmetic vs
the model-level flops_per_token, the >0.95 fabrication guard, structured
JSONL schema, in-loop profiler xplane capture, flight-recorder dump on an
injected hang@ watchdog fire, --timing_log_option handling, the folded
timers.report(), and the tools/telemetry_report.py summarizer."""

import argparse
import glob
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from megatron_llm_tpu import global_vars, telemetry
from megatron_llm_tpu.config import ParallelConfig, TrainConfig
from megatron_llm_tpu.models.language_model import flops_per_token
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.parallel import sharding as sh
from megatron_llm_tpu.resilience import (
    FaultInjector,
    HangWatchdog,
    ResilienceConfig,
    ResilienceManager,
)
from megatron_llm_tpu.telemetry import (
    MFU_SANITY_LIMIT,
    FlightRecorder,
    TELEMETRY_SCHEMA_VERSION,
    ThroughputCalculator,
    build_telemetry,
    peak_flops_for_kind,
    peak_flops_for_local_device,
)
from megatron_llm_tpu.timers import Timers
from megatron_llm_tpu.training import pretrain, training_log

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
                       padded_vocab_size=64, num_layers=1, hidden_size=32,
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
    """A parsed-args stand-in with just the telemetry group's fields."""
    base = dict(structured_log_dir=None, flight_recorder_size=64,
                profile=False, profile_step_start=2, profile_step_end=3,
                profile_dir=None, profiler_port=None)
    base.update(kw)
    return argparse.Namespace(**base)


# ---------------------------------------------------------------------------
# Throughput / MFU arithmetic
# ---------------------------------------------------------------------------

def test_peak_flops_lookup():
    assert peak_flops_for_kind("TPU v4") == 275e12
    assert peak_flops_for_kind("TPU v5 lite") == 197e12
    assert peak_flops_for_kind("TPU v5p chip") == 459e12
    assert peak_flops_for_kind("TPU v6e") == 918e12
    # a kind the table lacks is an error, never a default peak
    with pytest.raises(ValueError, match="TPU v9 mega"):
        peak_flops_for_kind("TPU v9 mega")
    with pytest.raises(ValueError):
        peak_flops_for_kind("cpu")
    # the CPU backend asks for no MFU at all
    assert peak_flops_for_local_device() is None


def test_mfu_arithmetic_matches_hand_computed_flops():
    cfg = llama_config("tiny", seq_length=16, max_position_embeddings=16,
                       padded_vocab_size=64, num_layers=1, hidden_size=32,
                       num_attention_heads=4, ffn_hidden_size=64)
    model = LlamaModel(cfg)
    fpt = model.flops_per_token()
    assert fpt == flops_per_token(cfg)
    # hand-computed for this exact tiny config: per-layer matmul params
    # (qkv + out-proj + glu mlp) + tied embedding, 6 flops/param/token
    # fwd+bwd, plus the 3x attention term
    qkv = 32 * (4 + 2 * 4) * 8
    proj = 4 * 8 * 32
    mlp_p = 32 * 64 * 2 + 64 * 32
    dense = 1 * (qkv + proj + mlp_p)
    emb = 64 * 32
    attn = 1 * 2 * 2 * 16 * 4 * 8
    assert fpt == pytest.approx(6.0 * (dense + emb) + 3.0 * attn)

    calc = ThroughputCalculator(flops_per_token=fpt, device_count=8,
                                peak_flops=1e12)
    out = calc.compute(tokens=4096, elapsed_secs=0.5)
    tps = 4096 / 0.5
    assert out["tokens_per_sec"] == pytest.approx(tps)
    assert out["tokens_per_sec_per_device"] == pytest.approx(tps / 8)
    assert out["tflops_per_device"] == pytest.approx(
        tps * fpt / 8 / 1e12)
    assert out["mfu"] == pytest.approx(tps * fpt / 8 / 1e12 / 1.0)


def test_mfu_guard_and_unknown_peak():
    # impossible MFU (the bench's >0.95 fabrication guard): reported null,
    # never a made-up number — but the achieved TFLOPs stays (it is a
    # measurement, not a ratio against a peak)
    calc = ThroughputCalculator(flops_per_token=1e9, device_count=1,
                                peak_flops=1e9)
    out = calc.compute(tokens=100, elapsed_secs=0.001)   # mfu would be 1e5
    assert out["mfu"] is None
    assert out["tflops_per_device"] is not None
    assert MFU_SANITY_LIMIT == 0.95
    # unknown peak (CPU): mfu null, throughput still reported
    calc = ThroughputCalculator(flops_per_token=1e9, device_count=1,
                                peak_flops=None)
    out = calc.compute(tokens=100, elapsed_secs=1.0)
    assert out["mfu"] is None
    assert out["tokens_per_sec"] == pytest.approx(100.0)


def test_from_model_on_cpu_never_fabricates(utils):
    model, _, _ = _setup(utils)
    calc = ThroughputCalculator.from_model(model)
    assert calc.flops_per_token == pytest.approx(model.flops_per_token())
    assert calc.peak_flops is None          # CPU backend
    assert calc.compute(1000, 0.1)["mfu"] is None


def test_training_log_prints_throughput(capsys):
    training_log(5, 10, {"lm loss": 1.0}, elapsed_per_iter=0.5,
                 tokens_per_iter=1000, lr=1e-3,
                 throughput={"tokens_per_sec": 2000.0,
                             "tokens_per_sec_per_device": 250.0,
                             "tflops_per_device": 12.5, "mfu": 0.42})
    out = capsys.readouterr().out
    assert "tokens per second per device: 250.0" in out
    assert "TFLOPs per device: 12.5" in out
    assert "MFU: 42.0%" in out
    # null mfu (CPU / guard): the field is omitted, not printed as 0
    training_log(5, 10, {"lm loss": 1.0}, 0.5, 1000, 1e-3,
                 throughput={"tokens_per_sec": 2000.0,
                             "tokens_per_sec_per_device": 250.0,
                             "tflops_per_device": None, "mfu": None})
    assert "MFU" not in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_bounded(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record({"iteration": i})
    assert len(fr) == 4
    assert [r["iteration"] for r in fr.records()] == [6, 7, 8, 9]
    path = fr.dump(str(tmp_path / "fr.json"), reason="unit test")
    payload = json.loads(open(path).read())
    assert payload["reason"] == "unit test"
    assert [r["iteration"] for r in payload["records"]] == [6, 7, 8, 9]


# ---------------------------------------------------------------------------
# JSONL stream + in-loop profiler (the acceptance-criteria tiny run)
# ---------------------------------------------------------------------------

def test_structured_stream_schema_and_profiler_xplane(utils, tmp_path):
    """CPU tiny run with --structured_log_dir + --profile_step_start 2
    --profile_step_end 3: JSONL records carry tokens_per_sec_per_device
    and mfu (null on CPU, never fabricated) and the profiler leaves an
    xplane under --profile_dir."""
    model, params, it = _setup(utils)
    log_dir = str(tmp_path / "telemetry")
    prof_dir = str(tmp_path / "trace")
    tel = build_telemetry(
        _telemetry_args(structured_log_dir=log_dir, profile=True,
                        profile_step_start=2, profile_step_end=3,
                        profile_dir=prof_dir, flight_recorder_size=8),
        model)
    try:
        pretrain(model, params, _tc(4), ParallelConfig(), it(),
                 log_interval=1, telemetry=tel)
    finally:
        tel.close()

    planes = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                       recursive=True)
    assert planes and os.path.getsize(planes[0]) > 0

    lines = open(os.path.join(log_dir, "telemetry.jsonl")).readlines()
    records = [json.loads(l) for l in lines]
    # the compiled step says once what it counted in its own text (PR 31);
    # every other record is a log boundary
    (program,) = [r for r in records if r["kind"] == "train_step_program"]
    assert program["dp_grad_reductions_in_loops"] == 0
    records = [r for r in records if r["kind"] == "log"]
    assert [r["iteration"] for r in records] == [1, 2, 3, 4]
    golden_keys = {"schema", "kind", "time_unix", "iteration",
                   "train_iters", "lm_loss", "grad_norm", "loss_scale",
                   "skipped_iter", "learning_rate", "step_time_secs",
                   "tokens_per_iter", "tokens_per_sec",
                   "tokens_per_sec_per_device", "tflops_per_device",
                   "mfu", "memory", "recovery"}
    for r in records:
        assert golden_keys <= set(r), golden_keys - set(r)
        assert r["schema"] == TELEMETRY_SCHEMA_VERSION
        assert r["kind"] == "log"
        assert r["mfu"] is None                      # CPU: never fabricated
        assert r["tokens_per_sec_per_device"] > 0
        assert r["step_time_secs"] > 0
        assert isinstance(r["memory"], dict)
        assert set(r["recovery"]) == {"rewinds", "save_retries",
                                      "watchdog_fires", "signal_saves"}
    # the flight recorder saw both per-iteration dispatch entries and the
    # full log records
    kinds = {rec["kind"] for rec in tel.stream.flight_recorder.records()}
    # (and, since PR 49, the one ``startup`` record written at "ready")
    assert kinds == {"dispatch", "log", "startup"}
    # run aggregates for the wandb/TB finish() summary
    s = tel.stream.summary()
    assert s["log_boundaries"] == 4 and s["mean_mfu"] is None
    assert s["mean_tokens_per_sec_per_device"] > 0


def test_flight_recorder_dump_on_watchdog_fire(utils, tmp_path):
    """An injected hang@3 fires the watchdog, whose stack-dump path dumps
    the flight recorder (last K step records) next to the JSONL stream."""
    model, params, it = _setup(utils)
    log_dir = str(tmp_path / "telemetry")
    tel = build_telemetry(
        _telemetry_args(structured_log_dir=log_dir,
                        flight_recorder_size=8), model)
    wd = HangWatchdog(timeout_secs=0.5, hard_exit=False,
                      poll_interval=0.05, printer=lambda s: None)
    rm = ResilienceManager(ResilienceConfig(snapshot_interval=1),
                           injector=FaultInjector.from_spec("hang@3:2.0"),
                           watchdog=wd)
    try:
        pretrain(model, params, _tc(4), ParallelConfig(), it(),
                 log_interval=1, resilience=rm, telemetry=tel)
    finally:
        rm.close()
        tel.close()
    assert wd.fired
    dump_path = os.path.join(log_dir, "flight_recorder.json")
    assert os.path.exists(dump_path)
    payload = json.loads(open(dump_path).read())
    assert payload["reason"] == "stack dump"
    assert payload["records"]
    # the dump happened mid-hang: its newest record predates iteration 3's
    # completion, proving it captured the state at fire time
    iters = [r.get("iteration") for r in payload["records"]
             if r.get("iteration") is not None]
    assert iters and max(iters) <= 3
    # and the printed report inlines the recorder section
    assert "flight recorder" in wd.last_dump


# ---------------------------------------------------------------------------
# --timing_log_option + timers.report
# ---------------------------------------------------------------------------

def _spin(timers, name, secs=0.01):
    import time as _t
    t = timers(name, log_level=0)
    t.start()
    _t.sleep(secs)
    t.stop()


def test_timing_log_option_changes_output():
    outs = {}
    for opt in ("minmax", "max", "all"):
        tm = Timers(log_level=2, log_option=opt)
        _spin(tm, "train-step")
        lines = []
        tm.log(printer=lines.append)
        outs[opt] = lines[0]
    assert outs["minmax"].startswith("(min, max) time (ms)")
    assert outs["max"].startswith("max time (ms)")
    assert outs["all"].startswith("time (ms) across hosts")
    # demonstrably different outputs, same timers
    assert len({o.split("|")[0] for o in outs.values()}) == 3
    # greppability contract (test_train_flags relies on it): every variant
    # keeps the literal "time (ms)"
    assert all("time (ms)" in o for o in outs.values())
    # single host: the entry degenerates to the plain value, no tuple
    assert "(min" not in outs["minmax"].split("|")[1]
    with pytest.raises(ValueError):
        Timers(log_option="median")


def test_timers_write_single_host_plain_keys():
    tm = Timers(log_level=2, log_option="minmax")
    _spin(tm, "train-step")
    rows = []

    class W:
        def add_scalar(self, k, v, it):
            rows.append((k, v, it))

    tm.write(["train-step"], W(), iteration=7)
    assert len(rows) == 1
    k, v, it = rows[0]
    assert k == "train-step-time" and v > 0 and it == 7


def test_timers_report_single_snapshot():
    """report() feeds writer + console from ONE elapsed read and resets —
    the write()-before-log() ordering trap is gone."""
    tm = Timers(log_level=2, log_option="minmax")
    _spin(tm, "train-step")
    rows, lines = [], []

    class W:
        def add_scalar(self, k, v, it):
            rows.append((k, v, it))

    tm.report(W(), iteration=3, normalizer=2.0, printer=lines.append)
    assert rows and lines
    written = rows[0][1]
    printed_ms = float(lines[0].split("train-step:")[1].strip())
    # the printed value is rounded to 2 decimals
    assert printed_ms == pytest.approx(written * 1000.0, abs=0.006)
    # the snapshot reset the accumulator: a second report is a no-op
    rows.clear()
    lines.clear()
    tm.report(W(), iteration=4, printer=lines.append)
    assert tm.get_elapsed(["train-step"], reset=False)["train-step"] == 0.0


# ---------------------------------------------------------------------------
# tools/telemetry_report.py
# ---------------------------------------------------------------------------

def _synthetic_stream(path, n=6):
    with open(path, "w") as f:
        for i in range(1, n + 1):
            rec = {
                "schema": 1, "kind": "log", "iteration": i,
                "lm_loss": 2.0 / i, "grad_norm": 1.0,
                "step_time_secs": 0.1 * i,
                "tokens_per_sec_per_device": 100.0 + i,
                "mfu": 0.4 if i != 3 else None,
                "memory": {"bytes_in_use": 1 << 20},
                "recovery": {"rewinds": 1 if i >= 4 else 0,
                             "save_retries": 0, "watchdog_fires": 0,
                             "signal_saves": 0},
            }
            f.write(json.dumps(rec) + "\n")
        f.write("{truncated-by-crash\n")


def test_telemetry_report_tool(tmp_path):
    stream = tmp_path / "telemetry.jsonl"
    _synthetic_stream(str(stream))
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "step time p50:" in r.stdout and "p95:" in r.stdout
    assert "mean MFU: 0.4" in r.stdout
    assert "recovery events:" in r.stdout
    assert "iteration 4: rewinds+1" in r.stdout
    assert "skipped 1 unparseable line" in r.stderr

    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         str(stream), "--json"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    agg = json.loads(r.stdout)["aggregates"]
    assert agg["log_boundaries"] == 6
    assert agg["p50_step_time_secs"] == pytest.approx(0.3, abs=0.11)
    assert agg["p95_step_time_secs"] == pytest.approx(0.6, abs=0.11)
    assert agg["mean_mfu"] == pytest.approx(0.4)
    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "telemetry_report.py"),
         str(tmp_path / "missing")],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r2.returncode == 2
