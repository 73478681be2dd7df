"""Engine-loop goodput profiler (serving/loop_profiler.py): scripted-
clock phase accounting (marks tile the dispatch, phases sum to wall by
construction), gap/idle/stall semantics with the flight recorder,
periodic ``engine_loop_stats`` emission, tracer sub-spans, agreement
across the three surfaces (``stats()`` / JSONL / serve_report), and the
slow overhead gate the sweep's ``serve_loop_overhead`` step runs.
"""

import json
import os
import sys
import time

import pytest

from megatron_llm_tpu import telemetry, tracing
from megatron_llm_tpu.serving import LOOP_PHASES, LoopProfiler

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import serve_report  # noqa: E402


class _Clock:
    """Scripted monotonic clock (the GoodputAccounter test pattern)."""

    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> float:
        self.t += dt
        return self.t


def _dispatch(prof, clock, kind="decode",
              schedule=0.001, build=0.002, device=0.010, emit=0.0005,
              draft=None):
    d = prof.begin()
    d.kind = kind
    clock.tick(schedule)
    d.mark("schedule")
    if draft is not None:
        clock.tick(draft)
        d.mark("draft")
    clock.tick(build)
    d.mark("build_inputs")
    clock.tick(device)
    d.mark("device")
    clock.tick(emit)
    prof.finish(d)


def test_scripted_clock_exact_phase_accounting():
    clock = _Clock()
    prof = LoopProfiler(clock=clock)
    _dispatch(prof, clock, kind="prefill")
    _dispatch(prof, clock, kind="verify", draft=0.003)

    assert prof.dispatches == 2
    assert prof.dispatches_by_kind == {"prefill": 1, "decode": 0,
                                       "verify": 1}
    assert prof.phase_secs["schedule"] == pytest.approx(0.002)
    assert prof.phase_secs["draft"] == pytest.approx(0.003)
    assert prof.phase_secs["build_inputs"] == pytest.approx(0.004)
    assert prof.phase_secs["device"] == pytest.approx(0.020)
    assert prof.phase_secs["emit"] == pytest.approx(0.001)
    # marks tile [begin, finish]: the phases sum to wall EXACTLY, far
    # inside the 5% acceptance bound
    assert sum(prof.phase_secs.values()) == pytest.approx(
        prof.wall_secs, rel=1e-9)
    # back-to-back dispatches on a scripted clock: zero gap
    assert prof.gap_secs == 0.0

    s = prof.stats()
    assert s["device_secs"] == pytest.approx(0.020)
    assert s["host_secs"] == pytest.approx(s["wall_secs"] - 0.020)
    want_busy = 100.0 * 0.020 / s["wall_secs"]
    assert s["device_busy_pct"] == pytest.approx(want_busy, abs=1e-3)
    assert s["host_bubble_pct"] == pytest.approx(100 - want_busy,
                                                 abs=1e-3)


def test_gap_idle_and_stall_semantics(tmp_path):
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    clock = _Clock()
    prof = LoopProfiler(clock=clock, stall_threshold_secs=0.5,
                        emit_every_dispatches=10_000,
                        emit_interval_secs=10_000.0)
    try:
        _dispatch(prof, clock)
        # a sub-threshold gap accumulates but is not a stall
        clock.tick(0.3)
        _dispatch(prof, clock)
        assert prof.gap_secs == pytest.approx(0.3)
        assert prof.stalls == 0

        # unarmed (pre-warmup): even a huge gap is not a stall
        clock.tick(5.0)
        _dispatch(prof, clock)
        assert prof.stalls == 0

        # idle() breaks the chain: an empty-queue wait is not a gap
        prof.idle()
        clock.tick(60.0)
        gaps_before = prof.gap_secs
        _dispatch(prof, clock)
        assert prof.gap_secs == pytest.approx(gaps_before)

        # armed + over threshold: counted and flight-recorded
        prof.stall_armed = True
        clock.tick(0.8)
        _dispatch(prof, clock, kind="prefill")
        assert prof.stalls == 1
        stallrecs = [r for r in stream.flight_recorder.records()
                     if r.get("kind") == "loop_stall"]
        assert len(stallrecs) == 1
        assert stallrecs[0]["gap_secs"] == pytest.approx(0.8)
        assert stallrecs[0]["threshold_secs"] == 0.5
        assert stallrecs[0]["dispatch_kind"] == "prefill"
    finally:
        telemetry.install_stream(None)
        stream.close()


def test_finish_tail_folds_into_emit_and_double_mark_accumulates():
    clock = _Clock()
    prof = LoopProfiler(clock=clock)
    d = prof.begin()
    clock.tick(0.001)
    d.mark("device")
    clock.tick(0.002)
    d.mark("emit")          # explicit emit mark ...
    clock.tick(0.003)
    prof.finish(d)          # ... and the tail folds into the same phase
    assert prof.phase_secs["emit"] == pytest.approx(0.005)
    assert prof.wall_secs == pytest.approx(0.006)


def test_maybe_emit_cadence_and_jsonl_schema(tmp_path):
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    clock = _Clock()
    prof = LoopProfiler(clock=clock, emit_every_dispatches=2,
                        emit_interval_secs=10_000.0)
    try:
        _dispatch(prof, clock)          # 1 fresh: not due
        _dispatch(prof, clock)          # 2 fresh: due at finish
        _dispatch(prof, clock)          # 1 fresh again: not due
        assert not prof.maybe_emit()    # still not due, no new record
        assert prof.maybe_emit(force=True)      # what engine.stop() does
    finally:
        telemetry.install_stream(None)
        stream.close()
    lines = [json.loads(ln) for ln in
             (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    loops = [r for r in lines if r.get("event") == "engine_loop_stats"]
    assert len(loops) >= 2
    first = loops[0]
    assert first["schema"] == telemetry.TELEMETRY_SCHEMA_VERSION
    assert first["kind"] == "serve"
    assert first["dispatches"] == 2
    # scalar p50/p95 travel; the bulky histogram snapshots do not
    assert "histograms" not in first
    assert set(first["phase_secs"]) == set(LOOP_PHASES)
    # the forced (engine-stop) record carries the final totals
    assert loops[-1]["dispatches"] == 3


def test_emit_interval_path(tmp_path):
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    clock = _Clock()
    prof = LoopProfiler(clock=clock, emit_every_dispatches=10_000,
                        emit_interval_secs=15.0)
    try:
        _dispatch(prof, clock)
        assert not prof.maybe_emit()            # fresh but interval not up
        clock.tick(20.0)
        assert prof.maybe_emit()                # interval elapsed
        clock.tick(20.0)
        assert not prof.maybe_emit()            # no new dispatch: not due
    finally:
        telemetry.install_stream(None)
        stream.close()


def test_tracer_subspans_tile_the_dispatch():
    tracer = tracing.SpanTracer()
    tracing.install_tracing(tracing.Tracing(tracer=tracer))
    clock = _Clock()
    prof = LoopProfiler(clock=clock)
    try:
        _dispatch(prof, clock, kind="verify", draft=0.003)
    finally:
        tracing.install_tracing(None)
    evs = [e for e in tracer.chrome_trace()["traceEvents"]
           if str(e.get("name", "")).startswith("loop.")]
    assert [e["name"] for e in evs] == [
        "loop.schedule", "loop.draft", "loop.build_inputs",
        "loop.device", "loop.emit"]
    assert all(e["cat"] == "serve_loop" for e in evs)
    # sub-spans tile: no overlap, no double counting — each starts where
    # the previous ended and durations sum to the dispatch wall-clock
    for prev, cur in zip(evs, evs[1:]):
        assert cur["ts"] == pytest.approx(prev["ts"] + prev["dur"],
                                          abs=1e-3)
    total_us = sum(e["dur"] for e in evs)
    assert total_us == pytest.approx(prof.wall_secs * 1e6, rel=1e-6)


def test_surfaces_agree_stats_jsonl_serve_report(tmp_path):
    """Acceptance: ``/metrics`` (stats()), the final ``engine_loop_stats``
    JSONL record, and serve_report's loop-goodput section report the
    same ``device_busy_pct``."""
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    clock = _Clock()
    prof = LoopProfiler(clock=clock, emit_every_dispatches=3,
                        emit_interval_secs=10_000.0)
    try:
        for i in range(7):
            _dispatch(prof, clock, kind="decode" if i % 2 else "prefill",
                      device=0.005 * (1 + i % 3))
            clock.tick(0.01)        # a little inter-dispatch gap
        prof.maybe_emit(force=True)     # what engine.stop() does
        stats = prof.stats()
    finally:
        telemetry.install_stream(None)
        stream.close()

    loops = serve_report.load_loop_stats(str(tmp_path))
    assert loops, "no engine_loop_stats records written"
    final = loops[-1]
    assert final["dispatches"] == stats["dispatches"] == 7
    assert final["device_busy_pct"] == stats["device_busy_pct"]
    assert final["host_bubble_pct"] == stats["host_bubble_pct"]

    report = serve_report.analyze([str(tmp_path)])
    lp = report["loop"]
    assert lp["dispatches"] == 7
    assert lp["device_busy_pct"] == pytest.approx(
        stats["device_busy_pct"], abs=1e-3)
    assert lp["stalls"] == stats["stalls"] == 0
    # phase shares cover the whole dispatch wall-clock
    assert sum(lp["phase_share"].values()) == pytest.approx(1.0, rel=1e-6)
    assert lp["bubble_trend"], "windowed trend missing"
    # and the rendering carries the section
    text = serve_report.render(report)
    assert "engine loop goodput" in text
    assert "device busy" in text


def test_serve_report_unchanged_on_pre_schema_10_logs(tmp_path):
    """A log with only request_done records (pre-10 shape) gets no
    ``loop`` key and renders exactly as before."""
    rec = {"schema": 9, "kind": "serve", "event": "request_done",
           "time_unix": 1.0, "latency_secs": 0.5, "ttft_secs": 0.1,
           "tpot_secs": 0.01, "finish_reason": "stop",
           "phases": {"queue_secs": 0.01, "admission_secs": 0.0,
                      "prefill_secs": 0.1, "decode_secs": 0.3,
                      "stream_write_secs": 0.01}}
    p = tmp_path / "telemetry.jsonl"
    p.write_text(json.dumps(rec) + "\n")
    report = serve_report.analyze([str(p)])
    assert "loop" not in report
    assert "engine loop goodput" not in serve_report.render(report)


def test_stats_shape_and_histograms():
    clock = _Clock()
    prof = LoopProfiler(clock=clock)
    _dispatch(prof, clock)
    s = prof.stats()
    for key in ("dispatches", "dispatches_by_kind", "wall_secs",
                "gap_secs", "device_secs", "host_secs", "phase_secs",
                "device_busy_pct", "host_bubble_pct", "stalls",
                "stall_threshold_secs", "window", "phase_p50_secs",
                "phase_p95_secs", "histograms"):
        assert key in s
    assert set(s["histograms"]) == {f"loop_{p}_secs" for p in LOOP_PHASES}
    snap = s["histograms"]["loop_device_secs"]
    assert snap["count"] == 1
    # the mergeable Histogram shape rides the Prometheus exposition
    text = telemetry.prometheus_exposition({"loop": s["histograms"]})
    assert "megatron_serve_loop_loop_device_secs_bucket" in text
    assert "megatron_serve_loop_loop_device_secs_count 1" in text
    # empty profiler: percentages are None, never a ZeroDivisionError
    empty = LoopProfiler(clock=clock).stats()
    assert empty["device_busy_pct"] is None
    assert empty["host_bubble_pct"] is None
    assert empty["window"]["device_busy_pct"] is None


def test_finish_survives_broken_telemetry(monkeypatch):
    """Diagnostics never kill the engine loop: a throwing flight
    recorder / stream is swallowed."""
    class _Boom:
        flight_recorder = property(lambda self: (_ for _ in ()).throw(
            RuntimeError("boom")))

        def emit(self, rec):
            raise RuntimeError("boom")

    clock = _Clock()
    prof = LoopProfiler(clock=clock, stall_threshold_secs=0.1,
                        emit_every_dispatches=1)
    prof.stall_armed = True
    monkeypatch.setattr(telemetry, "_ACTIVE_STREAM", _Boom())
    _dispatch(prof, clock)
    clock.tick(1.0)
    _dispatch(prof, clock)          # stall + emit paths both throw inside
    assert prof.dispatches == 2
    assert prof.stalls == 1


# ---------------------------------------------------------------------------
# overhead gate (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_loop_overhead_under_2pct():
    """Per-dispatch profiler bookkeeping (begin + a full set of phase
    marks + finish, with a live telemetry stream installed — the worst
    case) must cost < 2% of a real CPU dispatch of the tiny engine.
    The attribution may not become the bubble it measures."""
    import jax

    from megatron_llm_tpu.models.llama import LlamaModel, llama_config
    from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                          SamplingParams)

    # arm A: the real engine under traffic — mean dispatch wall-clock
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64,
        max_queue_depth=32, default_deadline_secs=0.0))
    eng.warmup()
    eng.start()
    try:
        reqs = [eng.submit([1 + i, 2, 3, 4],
                           SamplingParams(max_new_tokens=12,
                                          temperature=0.0, eod_id=63))
                for i in range(8)]
        for r in reqs:
            r.result(timeout=180)
        loop = eng.stats()["loop"]
    finally:
        eng.stop()
    assert loop["dispatches"] > 0
    mean_dispatch_secs = loop["wall_secs"] / loop["dispatches"]

    # arm B: the profiler alone, same dispatch protocol, tight loop
    stream = telemetry.TelemetryStream(None)    # no file, worst-case code
    telemetry.install_stream(stream)
    try:
        prof = LoopProfiler()
        prof.stall_armed = True
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            d = prof.begin()
            d.mark("schedule")
            d.mark("draft")
            d.mark("build_inputs")
            d.mark("device")
            prof.finish(d)
        cost_per_dispatch = (time.perf_counter() - t0) / n
    finally:
        telemetry.install_stream(None)
        stream.close()
    frac = cost_per_dispatch / mean_dispatch_secs
    assert frac < 0.02, (
        f"profiler bookkeeping {cost_per_dispatch * 1e6:.1f}us/dispatch "
        f"= {frac * 100:.2f}% of a {mean_dispatch_secs * 1e3:.2f}ms "
        f"CPU dispatch (gate: < 2%)")
