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
              schedule=0.001, build=0.002, dispatch=0.004, fetch=0.006,
              emit=0.0005, draft=None):
    d = prof.begin()
    d.kind = kind
    clock.tick(schedule)
    d.mark("schedule")
    if draft is not None:
        clock.tick(draft)
        d.mark("draft")
    clock.tick(build)
    d.mark("build_inputs")
    clock.tick(dispatch)
    d.mark("dispatch")
    clock.tick(fetch)
    d.mark("fetch")
    clock.tick(emit)
    prof.finish(d)
    return d


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
    assert prof.phase_secs["dispatch"] == pytest.approx(0.008)
    assert prof.phase_secs["fetch"] == pytest.approx(0.012)
    assert prof.phase_secs["emit"] == pytest.approx(0.001)
    # marks tile [begin, finish]: the phases sum to wall EXACTLY, far
    # inside the 5% acceptance bound
    assert sum(prof.phase_secs.values()) == pytest.approx(
        prof.wall_secs, rel=1e-9)
    # back-to-back dispatches on a scripted clock: zero gap
    assert prof.gap_secs == 0.0

    s = prof.stats()
    # dispatch + fetch is what the host WAITED: never device time
    assert s["wait_secs"] == pytest.approx(0.020)
    # what the host did NOT wait is the wall less that; no field of its
    # own (nothing read one)
    assert s["wall_secs"] - s["wait_secs"] == pytest.approx(
        sum(v for p, v in s["phase_secs"].items()
            if p not in ("dispatch", "fetch")))
    assert "host_secs" not in s and "stall_threshold_secs" not in s
    want_wait = 100.0 * 0.020 / s["wall_secs"]
    assert s["wait_pct"] == pytest.approx(want_wait, abs=1e-3)
    assert s["host_bubble_pct"] == pytest.approx(100 - want_wait,
                                                 abs=1e-3)
    assert "device_secs" not in s and "device_busy_pct" not in s


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
    d.mark("fetch")
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
        "loop.dispatch", "loop.fetch", "loop.emit"]
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
    same ``wait_pct``."""
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    clock = _Clock()
    prof = LoopProfiler(clock=clock, emit_every_dispatches=3,
                        emit_interval_secs=10_000.0)
    try:
        for i in range(7):
            _dispatch(prof, clock, kind="decode" if i % 2 else "prefill",
                      fetch=0.005 * (1 + i % 3))
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
    assert final["wait_pct"] == stats["wait_pct"]
    assert final["host_bubble_pct"] == stats["host_bubble_pct"]

    report = serve_report.analyze([str(tmp_path)])
    lp = report["loop"]
    assert lp["dispatches"] == 7
    assert lp["wait_pct"] == pytest.approx(stats["wait_pct"], abs=1e-3)
    assert lp["stalls"] == stats["stalls"] == 0
    # phase shares cover the whole dispatch wall-clock
    assert sum(lp["phase_share"].values()) == pytest.approx(1.0, rel=1e-6)
    assert lp["bubble_trend"], "windowed trend missing"
    # and the rendering carries the section
    text = serve_report.render(report)
    assert "engine loop goodput" in text
    assert "dispatch+fetch wait" in text


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
                "gap_secs", "wait_secs", "phase_secs",
                "wait_pct", "host_bubble_pct", "stalls",
                "window", "phase_p50_secs",
                "phase_p95_secs", "histograms"):
        assert key in s
    assert set(s["histograms"]) == {f"loop_{p}_secs" for p in LOOP_PHASES}
    snap = s["histograms"]["loop_fetch_secs"]
    assert snap["count"] == 1
    # the mergeable Histogram shape rides the Prometheus exposition
    text = telemetry.prometheus_exposition({"loop": s["histograms"]})
    assert "megatron_serve_loop_loop_fetch_secs_bucket" in text
    assert "megatron_serve_loop_loop_fetch_secs_count 1" in text
    # empty profiler: percentages are None, never a ZeroDivisionError
    empty = LoopProfiler(clock=clock).stats()
    assert empty["wait_pct"] is None
    assert empty["host_bubble_pct"] is None
    assert empty["window"]["wait_pct"] is None


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
# a record is a span: absolute times, what it worked on, a bounded ring,
# reachable without the engine, and on the profiler's clock
# ---------------------------------------------------------------------------

def test_marks_tile_begin_to_finish_with_absolute_times():
    clock = _Clock()
    prof = LoopProfiler(clock=clock)
    clock.tick(0.25)
    t0 = clock.t
    d = _dispatch(prof, clock, kind="verify", draft=0.003)
    # boundaries are stamps of the injected clock itself, not durations
    assert d.begin == t0
    assert d.phase_start("schedule") == d.begin
    assert d.phase_end("schedule") == pytest.approx(t0 + 0.001)
    assert d.phase_start("dispatch") == pytest.approx(t0 + 0.006)
    assert d.phase_end("dispatch") == pytest.approx(t0 + 0.010)
    assert d.phase_end("fetch") == pytest.approx(t0 + 0.016)
    assert d.end == clock.t
    # each phase starts where the one before it ended, first to last
    for a, b in zip(LOOP_PHASES, LOOP_PHASES[1:]):
        assert d.phase_start(b) == d.phase_end(a)
    assert sum(d.phase_secs(p) for p in LOOP_PHASES) == pytest.approx(
        d.wall_secs, rel=1e-12)
    assert d.wait_secs == pytest.approx(0.010)
    # a phase that was never marked takes no time and breaks no tiling
    d2 = _dispatch(prof, clock, kind="decode")
    assert d2.phase_secs("draft") == 0.0
    assert d2.phase_start("build_inputs") == d2.phase_end("schedule")


def test_ring_keeps_seq_kind_work_and_requests_and_stays_bounded():
    clock = _Clock()
    prof = LoopProfiler(clock=clock, ring_size=8)
    for i in range(20):
        d = prof.begin()
        d.kind = "prefill" if i % 2 else "decode"
        d.mark("schedule")
        if d.kind == "prefill":
            d.start, d.valid = 64 * i, 64
            d.requests, d.traces = (100 + i,), (f"t{i}",)
        else:
            d.rows, d.context_tokens = 3, 300 + i
            d.requests, d.traces = (1, 2, 3), ("a", "b")
        d.mark("build_inputs")
        clock.tick(0.001)
        d.mark("dispatch")
        clock.tick(0.002)
        d.mark("fetch")
        prof.finish(d)
        # the scheduler found nothing: no launch, no number used up
        prof.idle(prof.begin())
    recs = prof.records()
    assert len(recs) == 8 and prof.dispatches == 20
    assert [r.seq for r in recs] == list(range(12, 20))
    assert [r.seq for r in prof.records(last=3)] == [17, 18, 19]
    last = recs[-1]
    assert (last.kind, last.request, last.start, last.valid) == (
        "prefill", 119, 64 * 19, 64)
    assert last.requests == (119,) and last.traces == ("t19",)
    dec = recs[-2]
    assert (dec.kind, dec.rows, dec.context_tokens) == ("decode", 3, 318)
    assert dec.requests == (1, 2, 3)
    # the postmortem bundle's copy is JSON and carries the same span
    row = prof.ring_records(last=1)[0]
    json.dumps(row)
    assert row["seq"] == 19 and row["phases"]["fetch"] == pytest.approx(
        0.002)
    assert row["begin"] == last.begin
    # the default ring holds a window, its lead-in, a traced stretch and
    # a drain several times over
    assert LoopProfiler()._ring.maxlen >= 65536


def _tiny_engine(**kw):
    import jax

    from megatron_llm_tpu.models.llama import LlamaModel, llama_config
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64,
        max_queue_depth=32, default_deadline_secs=0.0, **kw))
    eng.warmup()
    return eng


def _serve(eng, n=3, prompt=20, new=6):
    from megatron_llm_tpu.serving import SamplingParams

    eng.start()
    try:
        reqs = [eng.submit([1 + i] + list(range(2, prompt + 1)),
                           SamplingParams(max_new_tokens=new,
                                          temperature=0.0, eod_id=63),
                           trace_id=f"trace{i}")
                for i in range(n)]
        for r in reqs:
            r.result(timeout=180)
    finally:
        eng.stop()
    return reqs


@pytest.mark.parametrize("speculative", [False, True])
def test_one_timer_per_launch_counters_are_the_records(speculative):
    """``decode_secs`` / ``prefill_secs`` are the summed ``dispatch`` +
    ``fetch`` of the launch records, not a clock of their own; every
    record says what it worked on; the request's span sits beside
    them."""
    from megatron_llm_tpu.serving.loop_profiler import live_profilers

    eng = _tiny_engine(speculative=speculative)
    reqs = _serve(eng)
    # reachable with no reference to the engine
    prof = next(p for p in live_profilers() if p is eng.loop_profiler)
    recs = prof.records()
    assert [r.seq for r in recs] == list(range(len(recs)))
    decode = [r for r in recs if r.kind in ("decode", "verify")]
    prefill = [r for r in recs if r.kind == "prefill"]
    assert {r.kind for r in decode} == {"verify" if speculative
                                        else "decode"}
    assert eng.decode_secs == pytest.approx(
        sum(r.wait_secs for r in decode), rel=1e-9)
    assert eng.prefill_secs == pytest.approx(
        sum(r.wait_secs for r in prefill), rel=1e-9)
    assert len(decode) == eng.decode_steps
    assert len(prefill) == eng.prefill_chunks
    ids = {r.id for r in reqs}
    for r in prefill[2:]:                   # the first two are warm-up's
        assert r.request in ids and r.requests == (r.request,)
        assert 0 < r.valid <= 16 and r.start % 16 == 0
        assert r.traces and r.traces[0].startswith("trace")
    served = [r for r in decode if set(r.requests) & ids]
    assert served and all(r.rows >= len(r.requests) > 0 for r in served)
    assert all(r.context_tokens >= 20 * len(r.requests) for r in served)
    # a request's own prefill launches: 20 prompt tokens are two chunks
    for q in reqs:
        own = [r for r in prefill if r.request == q.id]
        assert [r.start for r in own] == [0, 16]
        assert sum(r.valid for r in own) == 20
    spans = {s.request: s for s in prof.request_spans()}
    for q in reqs:
        s = spans[q.id]
        assert s.trace_id == q.trace_id
        assert s.submit <= s.admit <= s.first_token <= s.finish
        assert (s.prompt_tokens, s.answer_tokens) == (20, 6)
        assert s.finish_reason == "length"
        # the first token left inside its last prefill chunk's launch
        last = [r for r in prefill if r.request == q.id][-1]
        assert last.phase_start("dispatch") < s.first_token <= last.end


def _groups():
    from megatron_llm_tpu.serving import loop_profiler as lp

    return {name: getattr(lp, name) for name in (
        "MOE_FIELDS", "DSA_FIELDS", "MLA_FIELDS", "SSM_FIELDS",
        "CONV_FIELDS", "RETENTION_FIELDS", "DELTA_FIELDS", "KV_FIELDS",
        "WALK_FIELDS", "LOOP_FIELDS", "PREFILL_FIELDS", "HOST_FIELDS")}


@pytest.mark.parametrize("group", sorted(_groups()))
def test_a_counted_field_is_summed_where_the_launch_finishes(group):
    """The counted fields have ONE declaration, the groups' tuples
    together: a record that sets every one of them, finished twice, is in
    ``totals()`` twice, field by field, ``as_dict()`` carries each, and a
    record that set none counts nothing."""
    from megatron_llm_tpu.serving.loop_profiler import (COUNTED_FIELDS,
                                                        DispatchRecord)

    assert sorted(COUNTED_FIELDS) == sorted(sum(_groups().values(), ()))
    clock = _Clock()
    prof = LoopProfiler(clock=clock)
    assert prof.totals() == dict.fromkeys(COUNTED_FIELDS, 0)
    for _ in range(2):
        d = prof.begin()
        for i, f in enumerate(COUNTED_FIELDS):
            assert getattr(DispatchRecord, f) == 0
            setattr(d, f, 3 + i)
        clock.tick(0.001)
        prof.finish(d)
        prof.finish(prof.begin())       # a launch that counted nothing
    totals = prof.totals()
    for f in _groups()[group]:
        want = 3 + COUNTED_FIELDS.index(f)
        assert totals[f] == 2 * want
        assert totals[f] == sum(getattr(r, f) for r in prof.records())
        assert prof.records()[0].as_dict()[f] == want
    # the loop block of stats() carries the host's four and no other
    loop = prof.stats()
    assert {f: loop[f] for f in _groups()["HOST_FIELDS"]} == {
        f: totals[f] for f in _groups()["HOST_FIELDS"]}
    assert not set(loop) & (set(COUNTED_FIELDS)
                            - set(_groups()["HOST_FIELDS"]))


@pytest.mark.parametrize("family", ["granite", "keye", "kanana", "mellum",
                                    "olmoe", "mistral"])
def test_every_counter_of_stats_is_the_sum_over_the_ring(family):
    """After a few launches of a tiny engine every counted key of
    ``stats()`` (and the host's two in its ``loop`` block) equals the sum
    of that field over ``loop_profiler.records()``: the engine keeps no
    total of its own."""
    import importlib

    import jax

    from megatron_llm_tpu.models import MODEL_REGISTRY
    from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                          SamplingParams)
    from megatron_llm_tpu.serving.loop_profiler import (COUNTED_FIELDS,
                                                        HOST_FIELDS)

    config = getattr(importlib.import_module(
        "megatron_llm_tpu.models." + family), family + "_config")
    model = MODEL_REGISTRY[family](config("tiny", use_flash_attn=False))
    eng = InferenceEngine(model, model.init(jax.random.PRNGKey(0)),
                          EngineConfig(num_slots=3, block_size=8,
                                       max_model_len=96, prefill_chunk=16,
                                       preemption=False))
    reqs = [eng.submit([(5 * i + j) % 500 + 1 for j in range(n)],
                       SamplingParams(max_new_tokens=4, temperature=0.0))
            for i, n in enumerate((21, 37, 9))]
    while any(r.finish_reason is None for r in reqs):
        assert eng.step()
    stats, records = eng.stats(), eng.loop_profiler.records()
    assert len(records) == stats["loop"]["dispatches"] > 6
    moved = 0
    for f in COUNTED_FIELDS:
        where = stats["loop"] if f in HOST_FIELDS else stats
        assert where[f] == sum(getattr(r, f) for r in records), f
        assert not hasattr(eng, f), f
        moved += where[f] > 0
    # each family counts what its mechanisms are and the one thing every
    # family's chunks count (the head's rows: a request, a head), and
    # nothing else
    counted = {f.split("_")[0] for f in COUNTED_FIELDS if stats.get(f)}
    assert counted - {"prefill"} == {
        "granite": {"moe", "ssm", "walks"}, "keye": {"moe", "dsa", "walks"},
        "kanana": {"moe", "mla"}, "mellum": {"moe", "kv", "walks"},
        "olmoe": {"moe", "walks"}, "mistral": {"walks"}}[family]
    # the CPU runs the dense path: no walk multiplied in the pool's dtype
    assert stats["walks_native"] == 0
    assert stats["prefill_head_rows"] == stats["prefill_heads"] == len(reqs)
    assert moved >= 2


def test_the_two_lost_time_fields_go_where_every_counted_field_goes():
    """``compile_secs`` and ``gc_secs`` are two more names of
    HOST_FIELDS and nothing else: the record, ``as_dict()``, ``totals()``
    and the loop block of ``stats()`` carry them by the one
    declaration."""
    from megatron_llm_tpu.serving.loop_profiler import (COUNTED_FIELDS,
                                                        HOST_FIELDS)

    assert {"compile_secs", "gc_secs"} <= set(HOST_FIELDS) <= set(
        COUNTED_FIELDS)
    clock = _Clock()
    prof = LoopProfiler(clock=clock)
    d = prof.begin()
    assert d.compile_secs == 0.0 and d.gc_secs == 0.0
    d.compile_secs, d.gc_secs = 0.25, 0.5
    prof.finish(d)
    prof.finish(prof.begin())
    for where in (d.as_dict(), prof.totals(), prof.stats()):
        assert (where["compile_secs"], where["gc_secs"]) == (0.25, 0.5)
    idle = prof.records()[1].as_dict()
    assert (idle["compile_secs"], idle["gc_secs"]) == (0.0, 0.0)
    assert (idle["gap_compile_secs"], idle["gap_gc_secs"]) == (0.0, 0.0)


def test_a_forced_collection_lands_in_its_launch_and_in_no_other():
    import gc

    prof = LoopProfiler()
    other = LoopProfiler()              # a second engine's, with no launch
    gc.disable()                        # no collection but the forced ones
    try:
        prof.finish(prof.begin())
        d = prof.begin()
        gc.collect()
        prof.finish(d)
        prof.finish(prof.begin())
        gc.collect()                    # in the gap: the NEXT record's
        g = prof.begin()
        prof.finish(g)
        prof.idle()
        gc.collect()                    # the engine waits for work: nobody's
        prof.idle(prof.begin())
        last = prof.begin()
        prof.finish(last)
    finally:
        gc.enable()
    recs = prof.records()
    assert d.gc_secs > 0.0 and g.gap_gc_secs > 0.0
    assert [r.gc_secs for r in recs if r is not d] == [0.0] * 4
    assert [r.gap_gc_secs for r in recs if r is not g] == [0.0] * 4
    assert prof.totals()["gc_secs"] == d.gc_secs
    assert other.totals()["gc_secs"] == 0.0 and other._gap_gc > 0.0


def test_compile_events_are_a_union_on_the_launchs_thread():
    """The ledger's listener credits the open launch of the profiler
    whose loop runs on the compiling thread: nested events once, another
    thread's not at all, and with no launch open the gap's account."""
    import threading

    import jax
    import jax.numpy as jnp

    prof = LoopProfiler()
    prof.finish(prof.begin())
    d = prof.begin()

    @jax.jit
    def inner_of_a_launch(x):
        return jnp.cos(x) @ x

    t0 = time.perf_counter()
    jax.jit(lambda x: inner_of_a_launch(x) * 3.0)(
        jnp.ones((5, 5))).block_until_ready()
    took = time.perf_counter() - t0
    elsewhere = threading.Thread(target=lambda: jax.jit(
        lambda x: x - 7.0)(jnp.ones((3,))).block_until_ready())
    elsewhere.start()
    elsewhere.join()
    prof.finish(d)
    events = tracing.compile_ledger().between(t0, t0 + took)
    mine = [e for e in events if e[4] == threading.get_ident()]
    assert 0.0 < d.compile_secs <= took
    assert d.compile_secs == pytest.approx(
        tracing.CompileLedger.secs(mine), rel=1e-6)
    assert d.compile_secs < sum(e[3] - e[2] for e in mine)    # nested
    # in the gap: the next record's gap_compile_secs
    jax.jit(lambda x: x + 11.0)(jnp.ones((2,))).block_until_ready()
    g = prof.begin()
    prof.finish(g)
    assert g.gap_compile_secs > 0.0 and g.compile_secs == 0.0
    assert prof.totals()["compile_secs"] == d.compile_secs


def test_a_launch_far_over_its_kinds_median_is_a_stall(tmp_path):
    """The stall detector learns a launch's own length: dispatch + fetch
    over 3 times the running median of its kind and over 50 ms counts,
    with what it lost the time to in its flight-recorder entry."""
    from megatron_llm_tpu.serving import loop_profiler as lp

    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    try:
        clock = _Clock()
        prof = LoopProfiler(clock=clock)
        # warm-up: long launches (compiles), not armed, never stalls
        for _ in range(2):
            _dispatch(prof, clock, dispatch=5.0)
        assert prof.stalls == 0
        prof.stall_armed = True
        for _ in range(lp._MEDIAN_MIN_LAUNCHES):
            _dispatch(prof, clock, dispatch=0.004, fetch=0.016)
            _dispatch(prof, clock, kind="prefill", dispatch=0.1, fetch=0.1)
        assert prof.stalls == 0
        # 3x the median but under 50 ms: no stall; a prefill chunk of its
        # usual 200 ms: no stall
        _dispatch(prof, clock, dispatch=0.004, fetch=0.044)
        _dispatch(prof, clock, kind="prefill", dispatch=0.1, fetch=0.12)
        assert prof.stalls == 0
        d = prof.begin()
        d.kind = "decode"
        d.mark("build_inputs")
        clock.tick(0.3)
        d.mark("dispatch")
        d.compile_secs, d.gc_secs = 0.2, 0.05
        d.mark("fetch")
        prof.finish(d)
        assert prof.stalls == 1
        (rec,) = [r for r in stream.flight_recorder.records()
                  if r.get("kind") == "loop_stall"]
        assert rec["seq"] == d.seq and rec["dispatch_kind"] == "decode"
        assert rec["wait_secs"] == pytest.approx(0.3)
        assert rec["wait_median_secs"] == pytest.approx(0.02)
        assert (rec["compile_secs"], rec["gc_secs"]) == (0.2, 0.05)
        assert rec["gap_secs"] == 0.0
    finally:
        telemetry.install_stream(None)
        stream.close()


def test_an_engines_launches_say_what_they_compiled_and_when_they_slept(
        tmp_path):
    """A fresh engine's first chunk compiles its program inside the
    launch (``compile_secs`` > 0); warm-up's launches never count as
    stalls; the steady launches after it have exactly 0.0; and a launch
    made slow by a sleeping program counts in ``stalls``."""
    from megatron_llm_tpu.serving import loop_profiler as lp

    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    try:
        tracing.startup_begin()
        eng = _tiny_engine()
        prof = eng.loop_profiler
        warm = prof.records()
        assert warm[0].kind == "prefill" and warm[0].compile_secs > 0.0
        assert warm[0].compile_secs <= warm[0].wall_secs
        assert prof.stalls == 0 and prof.stall_armed
        # the timeline named warm-up's children by the programs traced
        _serve(eng, n=2, prompt=20, new=lp._MEDIAN_MIN_LAUNCHES + 2)
        kids = set(eng.stats()["startup"]["children"])
        assert {"warmup.engine_prefill", "warmup.engine_sample_first",
                "warmup.engine_decode", "warmup.engine_cow_copy"} <= kids
        steady = [r for r in prof.records() if r.seq > warm[-1].seq]
        assert len(steady) > lp._MEDIAN_MIN_LAUNCHES
        assert [r.compile_secs for r in steady] == [0.0] * len(steady)
        assert prof.totals()["compile_secs"] == pytest.approx(
            sum(r.compile_secs for r in warm))
        program, slept = eng._decode_step, []

        def sleepy(*args):
            if not slept:
                slept.append(eng.loop_profiler._seq)
                time.sleep(0.4)
            return program(*args)

        eng._decode_step = sleepy
        before = prof.stalls
        _serve(eng, n=1, prompt=12, new=4)
        assert prof.stalls > before
        found = [r for r in stream.flight_recorder.records()
                 if r.get("kind") == "loop_stall" and r["seq"] == slept[0]]
        assert found and found[0]["wait_secs"] >= 0.4
        assert found[0]["compile_secs"] == 0.0
        assert found[0]["wait_median_secs"] * lp.SLOW_FACTOR < 0.4
    finally:
        telemetry.install_stream(None)
        stream.close()


def test_registry_yields_the_live_profiler_without_the_engine():
    from megatron_llm_tpu.serving.loop_profiler import live_profilers

    clock = _Clock()
    busy = LoopProfiler(clock=clock)
    quiet = LoopProfiler(clock=clock)
    _dispatch(busy, clock)
    found = live_profilers()
    assert busy in found and quiet in found
    assert found.index(busy) < found.index(quiet)   # most launches first
    # a stopped engine's spans stay readable (the benchmark reads them
    # after engine.stop()), and the registry stays bounded: newer
    # profilers push the oldest out
    for _ in range(8):
        LoopProfiler(clock=clock)
    found = live_profilers()
    assert len(found) == 4 and busy not in found and quiet not in found


def test_loop_phases_are_on_the_profilers_clock(tmp_path):
    """The shared clock: under ``jax.profiler.start_trace`` every phase
    is a ``loop.<phase>`` event on the engine thread's line, carrying
    ``seq`` and ``kind``, and the events' starts equal the ring's
    ``perf_counter`` stamps up to ONE constant offset."""
    import glob

    import jax
    from jax.profiler import ProfileData

    eng = _tiny_engine()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _serve(eng)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    records = {r.seq: r for r in eng.loop_profiler.records()}
    events = {}                 # (phase, seq) -> (start seconds, stats)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("loop."):
                    stats = dict(ev.stats)
                    # an idle poll opens loop.schedule under the number
                    # of the launch to come: the last one is the launch's
                    events[(ev.name[5:], int(stats["seq"]))] = (
                        ev.start_ns * 1e-9, stats)
    assert not any(name.startswith("bench.") for name, _ in events)
    offsets = []
    for phase in ("dispatch", "fetch"):
        found = [(seq, at) for (p, seq), (at, st) in events.items()
                 if p == phase and st.get("kind") == "decode"]
        assert len(found) >= 5, f"no loop.{phase} events of kind decode"
        offsets += [at - records[seq].phase_start(phase)
                    for seq, at in found]
    assert max(offsets) - min(offsets) < 1e-3, (min(offsets), max(offsets))
    # the other phases ride the same offset, and kind is known from
    # build_inputs on (the scheduler has not decided before)
    seq, (at, st) = next((s, v) for (p, s), v in events.items()
                         if p == "build_inputs")
    assert at - records[seq].phase_start("build_inputs") == pytest.approx(
        offsets[0], abs=1e-3)
    assert st["kind"] == records[seq].kind
    assert "kind" not in next(v for (p, _), v in events.items()
                              if p == "schedule")[1]
    assert any(p == "gap" for p, _ in events)


KERNEL_NAMES = {
    "paged_attention_decode", "paged_attention_prefill",
    "paged_attention_decode_quant", "paged_attention_prefill_quant",
    "flash_attention_fwd", "flash_attention_bwd_fused",
    "flash_attention_bwd_dq", "flash_attention_bwd_dkv",
    "rmsnorm_fwd", "rmsnorm_bwd", "layernorm_fwd", "layernorm_bwd",
    "moe_experts",
    # learned sparse attention through the paged pool (PR 30): the
    # program is in the name, because a trace's operation carries none
    "dsa_index_scores_decode", "dsa_index_scores_prefill",
    "dsa_select_decode", "dsa_select_prefill",
    "paged_attention_sparse_decode", "paged_attention_prefill_masked",
    # the same walk launched for the window group of a model with a layer
    # type per layer (PR 32), which has no int8 pool
    "paged_attention_decode_window", "paged_attention_prefill_window",
    # the same walk over a latent pool (PR 36), which has neither an
    # int8 pool nor a window group
    "mla_attention_decode", "mla_attention_prefill",
    # and the same two under a mask of chosen rows (PR 61: the selection
    # over latents)
    "mla_attention_sparse_decode", "mla_attention_prefill_masked",
    # the decode step's state-space recurrence, in place over the live
    # rows (PR 45), under the scope ``ssm_step``
    "ssm_state_step", "retention_state_step",
    # a prefill chunk's power retention, phi formed in VMEM over the
    # slot's state where it lies (PR 55), under ``retention_chunk``
    "retention_state_chunk",
    # the decode step's gated delta rule, in place over the live rows
    # (PR 58), under ``delta_step``: the first caller of the walker
    # written once (``delta_step.walk_live_rows``)
    "delta_state_step",
    # a prefill chunk's gated delta rule, a block's systems solved on
    # the MXU beside the heads' state in VMEM (PR 59), under
    # ``delta_chunk``
    "delta_state_chunk",
}
PROGRAM_NAMES = {
    "_decode_step": "engine_decode", "_verify_step": "engine_verify",
    "_prefill_step": "engine_prefill",
    "_sample_first": "engine_sample_first", "_cow_copy": "engine_cow_copy",
    "_fetch_block": "engine_fetch_block", "_host_load": "engine_host_load",
}


def test_every_kernel_and_program_carries_its_stable_name():
    """Names are a contract with whoever reads a profile: every
    ``pallas_call`` under ``ops/pallas`` passes ``name=`` and the names
    that can come out are exactly ``KERNEL_NAMES``; the engine's jitted
    programs and the train step are called what the issue calls them."""
    import ast
    import glob
    import inspect

    from megatron_llm_tpu import training
    from megatron_llm_tpu.parallel import pipeline

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = set()
    calls = 0
    for path in glob.glob(os.path.join(root, "megatron_llm_tpu", "ops",
                                       "pallas", "*.py")):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                continue
            calls += 1
            kw = {k.arg: k.value for k in node.keywords}
            assert "name" in kw, f"{path}:{node.lineno}: pallas_call " \
                                 f"with no name="
            if isinstance(kw["name"], ast.Constant):
                names.add(kw["name"].value)
            # one call under either of two names (the latent chunk's walk,
            # with and without a mask of chosen rows)
            if isinstance(kw["name"], ast.IfExp):
                names |= {kw["name"].body.value, kw["name"].orelse.value}
        # the paged walk takes its name from whoever calls it: its two
        # public entries, the latent pool's decode step (no int8 pool, no
        # window group) and, under a mask of chosen keys, the selection
        # (``dsa_attention.py``, since PR 57: neither of those either)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            called = getattr(node.func, "id", getattr(node.func, "attr", ""))
            kw = {k.arg: k.value for k in node.keywords}
            if called == "_walk_call":
                names |= {kw["name"].value + suffix
                          for suffix in (("",) if {"value_width", "mask"}
                                         & set(kw) else
                                         ("", "_quant", "_window"))}
            # the selection's own kernels take theirs from the one entry
            # that calls them for the decode step and for a chunk
            if called in ("_index_scores", "_select"):
                names.add(kw["name"].value)
            # the live rows' walker takes its name from the step that
            # gives it its per-block update
            if called == "walk_live_rows":
                names.add(kw["name"].value)
    # 13 until the latent chunk got a walk of its own
    # (mla_attention_prefill), 14 until the state's step got a kernel,
    # 15 until a retention layer's did, 16 until its chunk's, 17 until
    # the selection's attention went into the shared walk, 16 until the
    # delta rule's step brought the live rows' walker, 17 until its
    # chunk got a kernel
    assert calls == 18
    assert names == KERNEL_NAMES

    eng = _tiny_engine()
    for attr, name in PROGRAM_NAMES.items():
        assert getattr(eng, attr).__name__ == name
    for build in (training.build_train_step,
                  pipeline.build_pipeline_train_step):
        assert "def train_step(" in inspect.getsource(build)


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
            d.mark("dispatch")
            d.mark("fetch")
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
