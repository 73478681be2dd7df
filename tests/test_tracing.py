"""Span tracing + goodput + straggler/recompile diagnostics
(megatron_llm_tpu/tracing.py): span nesting and ring eviction, the
Chrome trace_event export schema, goodput arithmetic on a synthetic
timeline, straggler flagging on synthetic per-host times, recompile
counting on a forced shape change, the tools/trace_report.py
summarizer, the acceptance-criteria tiny pretrain with --trace_dir,
rewind/rescue spans under injected faults, and the generation server's
/metrics + /health endpoints."""

import argparse
import functools
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from megatron_llm_tpu import global_vars, telemetry, tracing
from megatron_llm_tpu.config import ParallelConfig, TrainConfig
from megatron_llm_tpu.global_vars import get_counters
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.parallel import sharding as sh
from megatron_llm_tpu.resilience import (
    FaultInjector,
    HangWatchdog,
    ResilienceConfig,
    ResilienceManager,
    recovery_counters,
)
from megatron_llm_tpu.telemetry import build_telemetry
from megatron_llm_tpu.text_generation_server import (
    MegatronServer,
    ServerMetrics,
)
from megatron_llm_tpu.tracing import (
    GOODPUT_CATEGORIES,
    GoodputAccounter,
    RecompileDetector,
    SpanTracer,
    StragglerDetector,
    Tracing,
    build_tracing,
    install_detector,
    install_tracing,
)
from megatron_llm_tpu.training import pretrain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(ROOT, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_tracing_state():
    global_vars.reset_counters()
    telemetry.install_stream(None)
    install_tracing(None)
    yield
    install_tracing(None)
    install_detector(None)
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
    """A parsed-args stand-in with the telemetry group's fields
    (including the tracing flags this PR adds)."""
    base = dict(structured_log_dir=None, flight_recorder_size=64,
                profile=False, profile_step_start=2, profile_step_end=3,
                profile_dir=None, profiler_port=None, trace_dir=None,
                trace_buffer_size=100_000, straggler_threshold=1.5)
    base.update(kw)
    return argparse.Namespace(**base)


# ---------------------------------------------------------------------------
# SpanTracer: nesting, ring eviction, Chrome export schema
# ---------------------------------------------------------------------------

def test_span_nesting_and_ring_eviction():
    tr = SpanTracer(capacity=4)
    with tr.span("outer", "step"):
        with tr.span("inner", "checkpoint"):
            pass
    assert len(tr) == 2
    # the ring keeps the freshest events and counts evictions
    for i in range(10):
        with tr.span(f"s{i}", "other"):
            pass
    assert len(tr) == 4
    assert tr.dropped == 8            # 2 originals + s0..s5 evicted
    names = [e["name"] for e in tr.chrome_trace()["traceEvents"]
             if e["ph"] == "X"]
    assert names == ["s6", "s7", "s8", "s9"]


def test_span_handle_attaches_args():
    tr = SpanTracer()
    with tr.span("save", "checkpoint", iteration=3) as h:
        h.args["bytes"] = 1024
    (ev,) = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert ev["args"]["iteration"] == 3
    assert ev["args"]["bytes"] == 1024
    # outermost goodput span is tagged with the category it fed
    assert ev["args"]["goodput"] == "checkpoint"


def test_chrome_trace_schema():
    """The export is the Chrome trace_event JSON Perfetto loads: X/i
    events with µs ts/dur, small remapped tids, M metadata rows naming
    the process and threads, and otherData carrying the diagnostics."""
    tr = SpanTracer()
    with tr.span("step", "step", iteration=1):
        time.sleep(0.01)
    tr.instant("marker", "other", detail="x")
    doc = tr.chrome_trace(reason="unit test")
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"M", "X", "i"}
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    (x,) = [e for e in evs if e["ph"] == "X"]
    assert x["name"] == "step" and x["cat"] == "step"
    assert x["ts"] >= 0 and x["dur"] >= 10_000          # µs: >= 10 ms sleep
    assert isinstance(x["pid"], int) and x["tid"] == 0  # remapped small tid
    (i,) = [e for e in evs if e["ph"] == "i"]
    assert i["name"] == "marker" and i["s"] == "p"
    assert i["args"]["detail"] == "x"
    od = doc["otherData"]
    assert od["reason"] == "unit test"
    assert od["dropped_events"] == 0
    assert set(od["goodput"]) == ({f"{c}_secs" for c in GOODPUT_CATEGORIES}
                                  | {"other_secs", "wall_secs",
                                     "goodput_pct"})
    assert od["recompiles"] == 0 and od["straggler_events"] == 0
    # round-trips through json (Perfetto's parser reads a file)
    json.loads(json.dumps(doc))


def test_trace_write_atomic(tmp_path):
    tr = SpanTracer()
    with tr.span("step", "step"):
        pass
    path = tr.write(str(tmp_path / "trace.json"), reason="t")
    doc = json.loads(open(path).read())
    assert doc["otherData"]["reason"] == "t"
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


# ---------------------------------------------------------------------------
# Goodput arithmetic
# ---------------------------------------------------------------------------

def test_goodput_arithmetic_synthetic_timeline():
    """Injectable clock: 100s of wall, 60 step + 15 compile + 10
    checkpoint + 5 eval -> 10 unattributed, goodput 60%."""
    t = [0.0]
    g = GoodputAccounter(clock=lambda: t[0])
    g.add("step", 60.0)
    g.add("compile", 15.0)
    g.add("checkpoint", 10.0)
    g.add("eval", 5.0)
    t[0] = 100.0
    s = g.summary()
    assert s["wall_secs"] == pytest.approx(100.0)
    assert s["step_secs"] == pytest.approx(60.0)
    assert s["other_secs"] == pytest.approx(10.0)
    assert s["goodput_pct"] == pytest.approx(60.0)
    # move() reattributes (a compile inside a step span) and clamps
    assert g.move("step", "compile", 20.0) == pytest.approx(20.0)
    s = g.summary()
    assert s["step_secs"] == pytest.approx(40.0)
    assert s["compile_secs"] == pytest.approx(35.0)
    assert s["goodput_pct"] == pytest.approx(40.0)
    assert g.move("step", "compile", 1e9) == pytest.approx(40.0)  # clamp
    assert g.summary()["step_secs"] == 0.0


def test_nested_goodput_spans_never_double_count():
    """Outermost goodput span wins: a checkpoint_write inside a step
    span attributes nothing to 'checkpoint'; a non-goodput root (the
    'train' run span) does not shadow its children."""
    tr = SpanTracer()
    with tr.span("train", "run"):                 # trace-only category
        with tr.span("step", "step"):
            with tr.span("checkpoint_write", "checkpoint"):
                time.sleep(0.01)
    s = tr.goodput.summary()
    assert s["checkpoint_secs"] == 0.0
    assert s["step_secs"] >= 0.01
    with tr.span("checkpoint_save", "checkpoint"):
        time.sleep(0.01)
    assert tr.goodput.summary()["checkpoint_secs"] >= 0.01


# ---------------------------------------------------------------------------
# Straggler detection
# ---------------------------------------------------------------------------

def test_straggler_flagging_synthetic_hosts():
    lines = []
    tr = SpanTracer()
    det = StragglerDetector(threshold=1.5, tracer=tr,
                            printer=lines.append)
    found = det.check({"train-step": [0.1, 0.1, 0.5, 0.1]}, iteration=7)
    assert len(found) == 1
    ev = found[0]
    assert ev["host"] == 2 and ev["section"] == "train-step"
    assert ev["iteration"] == 7
    assert ev["ratio"] == pytest.approx(5.0)
    assert ev["median_secs"] == pytest.approx(0.1)
    assert det.total == 1
    assert get_counters()["straggler_events"] == 1
    assert "STRAGGLER host 2" in lines[0]
    (i,) = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] == "i"]
    assert i["name"] == "straggler" and i["args"]["host"] == 2


def test_straggler_no_flag_cases():
    det = StragglerDetector(threshold=1.5, printer=lambda s: None)
    # single host: no median to lag
    assert det.check({"train-step": [9.9]}, 1) == []
    # balanced hosts
    assert det.check({"train-step": [0.1, 0.1, 0.1, 0.1]}, 2) == []
    # above threshold but inside the min_secs noise floor
    assert det.check({"train-step": [0.001, 0.001, 0.004, 0.001]}, 3) == []
    assert det.total == 0 and get_counters()["straggler_events"] == 0


# ---------------------------------------------------------------------------
# Recompile detection
# ---------------------------------------------------------------------------

def test_recompile_counting_on_forced_shape_change():
    """A second input shape after mark_steady() retraces the jitted fn;
    the detector, a reader of the compile ledger, counts it as a
    recompile (>= 1 — the backend may also compile auxiliary constant
    programs) and carries the program's name."""
    tr = SpanTracer()
    det = RecompileDetector(tracer=tr)
    install_detector(det)
    ledger = tracing.compile_ledger()
    try:
        def reshaped(x):
            return x * 2.0 + 1.0
        f = jax.jit(reshaped)
        f(jnp.ones((4,))).block_until_ready()        # expected compile
        assert det.compiles >= 1 and det.recompiles == 0
        row = dict(ledger.rows()["reshaped"])
        assert (row["trace"], row["lower"], row["backend"]) == (1, 1, 1)
        det.mark_steady()
        f(jnp.ones((8,))).block_until_ready()        # forced retrace
        assert det.recompiles >= 1
        assert get_counters()["recompiles"] == det.recompiles
        assert det.events and det.events[-1]["kind"] == "recompile"
        assert det.events[-1]["program"] == "reshaped"
        # the new shape is a trace + lower + backend under the same name
        row = ledger.rows()["reshaped"]
        assert (row["trace"], row["lower"], row["backend"]) == (2, 2, 2)
        named = [e for e in tr.chrome_trace()["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "recompile"]
        assert named and named[-1]["args"]["program"] == "reshaped"
        n, secs = det.drain()
        assert n == det.compiles and secs >= 0.0
        assert det.drain() == (0, 0.0)
    finally:
        install_detector(None)


def test_recompile_pause_suppresses_expected_compiles():
    det = RecompileDetector()
    install_detector(det)
    try:
        det.mark_steady()
        det.pause()
        jax.jit(lambda x: x - 3.0)(jnp.ones((5,))).block_until_ready()
        assert det.recompiles == 0 and det.compiles == 0
        det.resume()
    finally:
        install_detector(None)


# ---------------------------------------------------------------------------
# The compile ledger
# ---------------------------------------------------------------------------

def _kernel(x, scale):
    for _ in range(6):          # enough work that a trace is no "hit"
        x = jnp.tanh(x) * scale + 1.0
    return x


_kernel_entry = jax.jit(_kernel, static_argnames=("scale",))


@pytest.mark.parametrize("jitted_entry", [False, True])
def test_ledger_names_a_kernel_traced_once_a_layer(jitted_entry):
    """PR 48's finding as a test: a kernel that a model calls in a
    Python loop of N layers through a jit made anew at every call is
    traced N times under its name; with ONE jitted entry it is traced
    once (the other layers hit jax's trace cache: ``trace_hit``)."""
    layers = 6
    ledger = tracing.compile_ledger()
    name = "_kernel"
    before = dict(ledger.rows().get(name) or {"trace": 0, "trace_hit": 0})
    mark = len(ledger.events)

    def model(x):
        for _ in range(layers):
            if jitted_entry:
                x = _kernel_entry(x, scale=2.0)
            else:
                x = jax.jit(functools.partial(_kernel, scale=2.0))(x)
        return x

    shape = (3, 5 + int(jitted_entry))
    jax.jit(model)(jnp.ones(shape)).block_until_ready()
    row = ledger.rows()[name]
    traced = row["trace"] - before["trace"]
    events = [e for e in ledger.events[mark:]
              if e[0] == "trace" and e[1] == name]
    assert len(events) == traced
    if jitted_entry:
        assert 1 <= traced < layers
        assert row["trace_hit"] - before["trace_hit"] >= layers - traced
    else:
        assert traced == layers
    # every event is on perf_counter, ends in the past, and is no hit
    now = time.perf_counter()
    assert all(e[2] < e[3] <= now
               and e[3] - e[2] >= tracing.TRACE_HIT_SECS for e in events)


def test_ledger_union_against_sum_for_a_nested_jit():
    """An inner jit is traced inside its caller's trace: the seconds of
    a stretch are the union of the events' intervals, which the sum of
    the events (and so of the programs' rows) exceeds."""
    ledger = tracing.compile_ledger()

    @jax.jit
    def inner_for_union(x):
        for _ in range(20):
            x = jnp.sin(x) @ x
        return x

    def outer_for_union(x):
        return inner_for_union(x) + 1.0

    t0 = time.perf_counter()
    jax.jit(outer_for_union)(jnp.ones((7, 7))).block_until_ready()
    t1 = time.perf_counter()
    events = ledger.between(t0, t1)
    traces = {e[1]: e for e in events if e[0] == "trace"}
    inner, outer = traces["inner_for_union"], traces["outer_for_union"]
    assert outer[2] <= inner[2] and inner[3] <= outer[3]      # nested
    both = [e for e in events if e[1] in ("inner_for_union",
                                          "outer_for_union")]
    union = ledger.secs(both, t0, t1, ("trace",))
    summed = sum(e[3] - e[2] for e in both if e[0] == "trace")
    assert union == pytest.approx(outer[3] - outer[2])
    assert summed == pytest.approx(union + inner[3] - inner[2])
    # the union of everything never exceeds the stretch it lies in
    assert 0.0 < ledger.secs(events, t0, t1) <= t1 - t0
    assert tracing.top_programs(both)[0]["program"] == "outer_for_union"


def test_union_secs_counts_each_instant_once():
    assert tracing.union_secs([]) == 0.0
    assert tracing.union_secs([(0, 4), (1, 2), (3, 6), (8, 9)]) == 7
    # a cache load inside its backend "compile", cut to a stretch
    events = [("cache_load", "p", 1.0, 1.5, 1), ("backend", "p", 0.9, 1.6, 1),
              ("trace", "q", 2.0, 3.0, 1)]
    secs = tracing.CompileLedger.secs
    assert secs(events) == pytest.approx(1.7)
    assert secs(events, kinds=("cache_load",)) == pytest.approx(0.5)
    assert secs(events, 1.2, 2.5) == pytest.approx(0.4 + 0.5)


def test_ledger_names_a_cache_load_after_its_backend_event():
    """A load from the persistent cache is reported with no name, inside
    the backend event that follows it on its thread."""
    ledger = tracing.CompileLedger(capacity=8)
    heard = []
    ledger.listeners += (lambda *a: heard.append(a),)
    ledger.hear("trace", "prog", 0.004)
    ledger.hear("trace", "prog", tracing.TRACE_HIT_SECS / 4)    # a hit
    ledger.hear("lower", "prog", 0.002)
    ledger.hear("cache_load", "", 0.010)
    ledger.hear("backend", "prog", 0.011)
    kinds = [(e[0], e[1]) for e in ledger.events]
    assert kinds == [("trace", "prog"), ("lower", "prog"),
                     ("cache_load", "prog"), ("backend", "prog")]
    row = ledger.rows()["prog"]
    assert (row["trace"], row["trace_hit"], row["lower"], row["backend"],
            row["cache_load"]) == (1, 1, 1, 1, 1)
    assert row["cache_load_secs"] == pytest.approx(0.010)
    assert [h[0] for h in heard] == ["trace", "lower", "cache_load",
                                     "backend"]
    # full: later events still count in the rows
    for _ in range(6):
        ledger.hear("lower", "prog", 0.001)
    assert len(ledger.events) == 8 and ledger.dropped == 2
    assert ledger.rows()["prog"]["lower"] == 7


# ---------------------------------------------------------------------------
# The start-up timeline
# ---------------------------------------------------------------------------

def test_startup_timeline_spans_line_and_record(tmp_path):
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    tr = SpanTracer()
    tracing.startup_begin()
    t_first = time.perf_counter()
    time.sleep(0.002)
    tracing.startup_completed("imports", t_first, time.perf_counter())
    with tracing.startup_span("initialize"):
        with tracing.startup_span("runtime_init"):
            time.sleep(0.002)
    # a tracer installed now is handed what ended before it
    install_tracing(Tracing(tracer=tr))
    with tracing.startup_span("build_model", model_name="toy"):
        jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones((6,))).block_until_ready()
    lines = []
    summary = tracing.startup_ready(printer=lines.append)
    stream.close()
    tl = tracing.startup_timeline()
    assert tl["summary"] is summary is tracing.startup_summary()
    names = [s[0] for s in tl["spans"]]
    assert names == ["imports", "initialize", "runtime_init", "build_model"]
    assert tl["first"] == t_first and tl["ready"] <= time.perf_counter()
    # top level: ordered, not overlapping, inside [first, ready]
    top = tracing._top_level(tl["spans"])
    assert [s[0] for s in top] == ["imports", "initialize", "build_model"]
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))
    assert list(summary["spans"]) == ["imports", "initialize", "build_model"]
    # the ledger's events, cut to the timeline; their union inside a
    # span never exceeds the span
    assert tl["events"] and all(e[3] > tl["first"] and e[2] < tl["ready"]
                                for e in tl["events"])
    for _, t0, t1, _ in tl["spans"]:
        assert tracing.CompileLedger.secs(tl["events"], t0, t1) <= t1 - t0
    assert summary["compile_secs"]["trace"] > 0.0
    assert summary["compile_union_secs"] <= summary["spans"]["build_model"]
    assert summary["top_programs"][0]["traced"] >= 1
    assert len(lines) == 1 and lines[0].startswith(" [startup] ready after")
    assert "build_model" in lines[0]
    recs = [json.loads(l) for l in
            open(os.path.join(str(tmp_path), "telemetry.jsonl"))]
    started = [r for r in recs if r["kind"] == "startup"]
    assert len(started) == 1
    assert started[0]["spans"] == summary["spans"]
    cats = {e["name"]: e["cat"] for e in tr.chrome_trace()["traceEvents"]
            if e["ph"] in ("X", "i")}
    assert {cats[n] for n in names + ["ready"]} == {"startup"}
    # nothing opened since: no second line, and the timeline stays
    assert tracing.startup_ready(printer=lines.append) is None
    assert len(lines) == 1 and tracing.startup_timeline() is tl


# ---------------------------------------------------------------------------
# build_tracing wiring
# ---------------------------------------------------------------------------

def test_build_tracing_wiring(tmp_path):
    assert build_tracing(_telemetry_args()) is None       # no --trace_dir
    t = build_tracing(_telemetry_args(trace_dir=str(tmp_path),
                                      trace_buffer_size=123,
                                      straggler_threshold=2.5))
    assert tracing.get_tracing() is t
    assert t.tracer.capacity == 123
    assert t.straggler.threshold == 2.5
    with tracing.span("step", "step"):
        pass
    t.close()
    assert tracing.get_tracing() is None
    doc = json.loads(open(tmp_path / "trace.json").read())
    assert doc["otherData"]["reason"] == "close"
    # module-level span() is a no-op once uninstalled
    with tracing.span("ignored", "step") as h:
        assert h is None
    assert tracing.dump_trace() is None


# ---------------------------------------------------------------------------
# tools/trace_report.py
# ---------------------------------------------------------------------------

def _synthetic_trace_dir(tmp_path):
    tr = SpanTracer()
    with tr.span("train", "run"):
        with tr.span("step", "step", iteration=1):
            time.sleep(0.02)
        with tr.span("checkpoint_save", "checkpoint", iteration=1):
            time.sleep(0.01)
    tr.instant("straggler", "straggler", iteration=1, host=2,
               section="train-step", secs=0.5, median_secs=0.1, ratio=5.0)
    get_counters()["straggler_events"] += 1
    tr.write(str(tmp_path / "trace.json"))
    with open(tmp_path / "telemetry.jsonl", "w") as f:
        for i in (1, 2):
            f.write(json.dumps({"kind": "log", "iteration": i,
                                "goodput_pct": 50.0 + i}) + "\n")


def test_trace_report_tool(tmp_path):
    _synthetic_trace_dir(tmp_path)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "goodput breakdown" in r.stdout
    assert "span coverage of traced wall-clock:" in r.stdout
    assert "straggler events: 1" in r.stdout
    assert "host 2" in r.stdout
    assert "goodput_pct per log boundary:" in r.stdout

    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(tmp_path / "trace.json"), "--json"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["coverage"] and doc["coverage"] > 0.9
    assert doc["straggler_timeline"][0]["host"] == 2
    # the root span is excluded from the top-spans list
    assert all(s["name"] != "train" for s in doc["top_spans"])
    assert doc["goodput_trend"] == [
        {"iteration": 1, "goodput_pct": 51.0},
        {"iteration": 2, "goodput_pct": 52.0}]

    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(tmp_path / "missing.json")],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r2.returncode == 2


# ---------------------------------------------------------------------------
# Acceptance: tiny pretrain with --trace_dir
# ---------------------------------------------------------------------------

def test_pretrain_trace_acceptance(utils, tmp_path):
    """The acceptance-criteria run: tiny CPU pretrain with --trace_dir
    writes a Perfetto-loadable trace whose spans cover >= 95% of the
    traced wall-clock, the JSONL stream carries goodput_pct (plus the
    recompile/straggler counters and the new interval_time_secs), and
    trace_report renders the breakdown."""
    model, params, it = _setup(utils)
    d = str(tmp_path)
    tel = build_telemetry(
        _telemetry_args(structured_log_dir=d, trace_dir=d), model)
    assert tel.tracing is not None
    try:
        pretrain(model, params, _tc(6), ParallelConfig(), it(),
                 log_interval=1, telemetry=tel,
                 save_dir=os.path.join(d, "ckpt"), save_interval=3)
        # run summary (the wandb/TB finish payload) carries the
        # aggregates while the run's tracing is still installed
        s = telemetry.run_summary()
    finally:
        tel.close()
    assert 0.0 < s["goodput_pct"] <= 100.0
    assert "recompiles" in s and "straggler_events" in s

    doc = json.loads(open(os.path.join(d, "trace.json")).read())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    # the loop's phases all opened spans
    assert {"train", "step", "data_next", "checkpoint_save",
            "checkpoint_write"} <= names
    assert len([e for e in xs if e["name"] == "train"]) == 1   # one root
    assert len([e for e in xs if e["name"] == "step"]) == 6

    report = _load_trace_report()
    assert report.coverage(doc) >= 0.95
    g = report.goodput_breakdown(doc)
    assert 0.0 < g["goodput_pct"] <= 100.0
    assert g["step_secs"] > 0 and g["checkpoint_secs"] > 0
    # wall-clock conservation: categories + other == wall
    parts = sum(g[f"{c}_secs"] for c in GOODPUT_CATEGORIES) + g["other_secs"]
    assert parts == pytest.approx(g["wall_secs"], rel=1e-6)

    records = [json.loads(l) for l in
               open(os.path.join(d, "telemetry.jsonl"))]
    # one event from the compiled step (PR 31), then the log boundaries
    assert records[0]["kind"] == "train_step_program"
    records = [r for r in records if r["kind"] == "log"]
    assert [r["iteration"] for r in records] == [1, 2, 3, 4, 5, 6]
    for r in records:
        assert 0.0 < r["goodput_pct"] <= 100.0
        assert set(r["goodput"]) >= {f"{c}_secs" for c in GOODPUT_CATEGORIES}
        assert r["recompiles"] >= 0 and r["straggler_events"] >= 0
        assert r["interval_time_secs"] >= r["step_time_secs"] > 0


def test_rewind_span_under_nan_injection(utils, tmp_path):
    """An injected nan@3 triggers a rewind; the trace shows it as a
    'rewind' span and the goodput breakdown bills the recovery time."""
    model, params, it = _setup(utils)
    tel = build_telemetry(_telemetry_args(trace_dir=str(tmp_path)), model)
    rm = ResilienceManager(
        ResilienceConfig(snapshot_interval=1, patience=1, spike_factor=0),
        injector=FaultInjector.from_spec("nan@3"))
    try:
        pretrain(model, params, _tc(6), ParallelConfig(), it(),
                 log_interval=1, telemetry=tel, resilience=rm)
    finally:
        rm.close()
        tel.close()
    assert recovery_counters()["rewinds"] == 1
    doc = json.loads(open(tmp_path / "trace.json").read())
    rewinds = [e for e in doc["traceEvents"]
               if e["ph"] == "X" and e["name"] == "rewind"]
    assert len(rewinds) == 1
    assert rewinds[0]["args"]["goodput"] == "rewind"
    assert doc["otherData"]["goodput"]["rewind_secs"] > 0


def test_rescue_and_watchdog_spans_under_hang(utils, tmp_path):
    """An injected hang@3 fires the watchdog: the trace records the
    'watchdog_fire' instant and the rescue checkpoint's 'rescue_save'
    span, and the stack-dump path exports the trace mid-run."""
    model, params, it = _setup(utils)
    tel = build_telemetry(_telemetry_args(trace_dir=str(tmp_path)), model)
    wd = HangWatchdog(timeout_secs=0.5, hard_exit=False,
                      poll_interval=0.05, printer=lambda s: None)
    rm = ResilienceManager(
        ResilienceConfig(snapshot_interval=1),
        injector=FaultInjector.from_spec("hang@3:2.0"),
        watchdog=wd)
    try:
        pretrain(model, params, _tc(4), ParallelConfig(), it(),
                 log_interval=1, save_dir=str(tmp_path / "ckpt"),
                 telemetry=tel, resilience=rm)
    finally:
        rm.close()
        tel.close()
    assert wd.fired
    doc = json.loads(open(tmp_path / "trace.json").read())
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "rescue_save" in names
    fires = [e for e in doc["traceEvents"]
             if e["ph"] == "i" and e["name"] == "watchdog_fire"]
    assert len(fires) == 1
    assert fires[0]["args"]["stalled_secs"] >= 0.5
    # the watchdog's stack dump mentioned the trace export
    assert "trace" in wd.last_dump


# ---------------------------------------------------------------------------
# Generation server /metrics + /health
# ---------------------------------------------------------------------------

def test_server_metrics_accounting():
    m = ServerMetrics(window=4)
    m.observe(0.1, 200, tokens=10)
    m.observe(0.2, 200, tokens=5)
    m.observe(0.3, 400)
    s = m.snapshot()
    assert s["requests"] == 3 and s["errors"] == 1
    assert s["tokens_generated"] == 15
    assert s["latency_p50_secs"] == pytest.approx(0.2)
    assert s["latency_p95_secs"] == pytest.approx(0.3)
    assert s["uptime_secs"] >= 0
    # bounded latency window
    for i in range(10):
        m.observe(float(i), 200)
    assert len(m._latencies) == 4
    assert ServerMetrics().snapshot()["latency_p50_secs"] is None


def test_server_metrics_concurrent_hooks_and_drain():
    """ServerMetrics is fed by the engine loop (request_done hook),
    bumped from HTTP-handler/signal contexts (note_drained), and read
    by /metrics threads — graft-lint threads/TH001 forced all three
    under ``_lock``.  Hammer them concurrently and require exact
    totals plus internally consistent snapshots."""
    m = ServerMetrics()
    rec = {"ttft_secs": 0.01, "tpot_secs": 0.002, "latency_secs": 0.05,
           "phases": {"queue_secs": 0.001}}
    n, feeders = 200, 4
    snaps = []

    def feed():
        for _ in range(n):
            m.observe_request_done(rec)

    def drain():
        for _ in range(n):
            m.note_drained()

    def read():
        for _ in range(50):
            snaps.append(m.snapshot())

    workers = [threading.Thread(target=feed) for _ in range(feeders)]
    workers += [threading.Thread(target=drain),
                threading.Thread(target=read)]
    for t in workers:
        t.start()
    for t in workers:
        t.join()
    s = m.snapshot()
    assert s["drained"] == n
    for name in ("ttft_secs", "tpot_secs", "e2e_secs",
                 "queue_wait_secs"):
        assert s["histograms"][name]["count"] == n * feeders
    # every mid-flight snapshot saw a consistent histogram: the bucket
    # counts it carries sum to the count it reports
    for sn in snaps:
        h = sn["histograms"]["e2e_secs"]
        assert sum(h["buckets"].values()) == h["count"]


def test_engine_watchdog_heartbeat_is_cross_thread_safe():
    """EngineWatchdog._last_progress is written by the engine loop and
    read by the watchdog's own thread (TH001 fix: both sides under
    ``_lock``).  A heartbeating 'engine' must never trip the watchdog;
    silencing the heartbeat must."""
    from megatron_llm_tpu.serving.resilience import EngineWatchdog

    fired = threading.Event()
    wd = EngineWatchdog(timeout_secs=0.2, has_work=lambda: True,
                        on_fire=fired.set, printer=lambda *_: None)
    wd.start()
    beating = threading.Event()
    beating.set()

    def engine_loop():
        while beating.is_set():
            wd.progress()
            time.sleep(0.01)

    t = threading.Thread(target=engine_loop, daemon=True)
    t.start()
    try:
        time.sleep(0.6)
        assert not fired.is_set(), \
            "watchdog fired despite a live heartbeat"
        beating.clear()
        t.join()
        assert fired.wait(timeout=5.0), \
            "watchdog never fired after the heartbeat stopped"
        assert wd.fires >= 1
    finally:
        beating.clear()
        wd.stop()


def test_server_health_and_metrics_endpoints():
    """GET /health and /metrics answer without touching the model (the
    generator is never invoked), so a None model is fine."""
    srv = MegatronServer(None, None, None)
    th = threading.Thread(
        target=lambda: srv.run(host="127.0.0.1", port=0), daemon=True)
    th.start()
    for _ in range(100):
        if getattr(srv, "httpd", None) is not None:
            break
        time.sleep(0.02)
    assert srv.httpd is not None
    port = srv.httpd.server_address[1]
    try:
        srv.metrics.observe(0.05, 200, tokens=7)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=5) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok" and health["uptime_secs"] >= 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
            snap = json.loads(r.read())
        assert snap["requests"] == 1 and snap["errors"] == 0
        assert snap["tokens_generated"] == 7
        assert snap["latency_p50_secs"] == pytest.approx(0.05)
    finally:
        srv.httpd.shutdown()
        th.join(timeout=5)
