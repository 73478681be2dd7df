"""The sources that read the engine's own span ring, on made-up rings
and a made-up trace: ``loop_phase`` (cut to the counted window),
``loop_device_latency`` (ring laid against the device trace on one
clock) and ``request_phase`` (request spans joined to their launches)."""
import json

import pytest

from conftest import ROOT  # noqa: F401 - puts the repo on sys.path
from harness import spec
from harness.context import Run
from harness.trace import DeviceTrace, Reduced
from harness.window import CounterSnapshot, Window
from megatron_llm_tpu.serving.loop_profiler import LoopProfiler, RequestSpan
from test_rehearsal import _run

loop_phase = spec.load_module("sources", "loop_phase")
latency = spec.load_module("sources", "loop_device_latency")
request_phase = spec.load_module("sources", "request_phase")

B = spec.load_benchmark()
NEW_SPAN_METRICS = {
    "loop_schedule_ms", "loop_build_inputs_ms", "loop_emit_ms",
    "loop_gap_ms", "decode_dispatch_ms", "prefill_dispatch_ms",
    "ttft_prefill_own_p50_ms", "ttft_interleave_p50_ms"}
NEW_TRACE_METRICS = {
    "decode_launch_latency_ms", "decode_fetch_latency_ms",
    "prefill_launch_latency_ms", "prefill_fetch_latency_ms",
    "loop_turnaround_ms", "idle_explained_pct"}
OFFSET = 1234.5         # trace clock minus host clock, seconds


class Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def launch(prof, clock, kind, *, gap=0.001, schedule=0.0005, build=0.001,
           dispatch=0.002, fetch=0.010, emit=0.0005, request=None,
           idle_before=False):
    """One scripted launch; times in seconds."""
    if idle_before:
        prof.idle(prof.begin())
    clock.t += gap
    d = prof.begin()
    d.kind = kind
    clock.t += schedule
    d.mark("schedule")
    if request is not None:
        d.requests = (request,)
    clock.t += build
    d.mark("build_inputs")
    clock.t += dispatch
    d.mark("dispatch")
    clock.t += fetch
    d.mark("fetch")
    clock.t += emit
    prof.finish(d)
    return d


def made_up_run(opened=None, closed=None):
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False,
              process_start=0.0)
    if opened is not None:
        run.window = Window(CounterSnapshot(opened, {}),
                            CounterSnapshot(closed, {}))
    return run


@pytest.fixture
def ring(monkeypatch):
    clock = Clock()
    prof = LoopProfiler(clock=clock)
    monkeypatch.setattr(loop_phase, "profiler", lambda: prof)
    return prof, clock


# -- loop_phase: the ring cut to the counted window -----------------------

def test_phase_means_over_the_launches_that_began_in_the_window(ring):
    prof, clock = ring
    before = launch(prof, clock, "decode", build=0.009)      # began before
    opened = clock.t + 0.0005       # inside the gap before the next one
    a = launch(prof, clock, "decode", build=0.001, dispatch=0.002)
    b = launch(prof, clock, "prefill", build=0.003, dispatch=0.004)
    c = launch(prof, clock, "decode", build=0.002, dispatch=0.006,
               idle_before=True, gap=5.0)
    closed = clock.t + 0.0002
    cut = launch(prof, clock, "decode", gap=0.0001, fetch=1.0)
    assert before.begin < opened <= a.begin and cut.begin < closed < cut.end
    run = made_up_run(opened, closed)

    def read(**kw):
        return loop_phase.read(run, **kw)
    # `before` began outside and is left out; `cut` began inside, is in
    assert read(phase="build_inputs") == pytest.approx(
        1000 * (0.001 + 0.003 + 0.002 + 0.001) / 4)
    assert read(phase="dispatch", kinds=["decode", "verify"],
                stat="median") == pytest.approx(2.0)
    assert read(phase="dispatch", kinds=["prefill"],
                stat="median") == pytest.approx(4.0)
    # the 5 s the engine waited for work is no gap: idle() broke the chain
    assert c.gap_secs == 0.0
    assert read(phase="gap") == pytest.approx(
        1000 * (0.001 + 0.001 + 0.0001) / 3)
    assert read(phase="emit") == pytest.approx(0.5)
    assert loop_phase.read(made_up_run(), phase="emit") is None
    assert loop_phase.read(made_up_run(0.0, 1.0), phase="emit") is None


# -- loop_device_latency: two clocks joined ---------------------------------

def traced(prof, clock, plan, hole=None):
    """Scripted launches, each with device operations from 0.5 ms after
    its dispatch start to 0.3 ms before its fetch end, a benchmark
    annotation stamped at dispatch start, and the made-up trace of it
    all on a clock ``OFFSET`` away."""
    ops, samples, spans, recs = [], {}, {}, []
    for kind, kw in plan:
        d = launch(prof, clock, kind, **kw)
        recs.append(d)
        ds, fe = d.phase_start("dispatch"), d.phase_end("fetch")
        ops.append((f"%fusion.{d.seq}", ds + 0.0005 + OFFSET,
                    fe - 0.0003 + OFFSET))
        name = ("bench.prefill_step" if kind == "prefill"
                else "bench.decode_step")
        samples.setdefault(name, []).append({"t": ds})
        spans.setdefault(name, []).append((ds + OFFSET,
                                           ds + OFFSET + 0.002))
    window = (recs[0].phase_start("dispatch") + OFFSET,
              recs[-1].phase_end("fetch") + OFFSET)
    run = made_up_run()
    run.step_samples = samples
    run.trace = Reduced(window, [DeviceTrace("/device:TPU:0", ops)], spans)
    return run, recs


def test_offset_is_recovered_exactly_and_refused_when_it_spreads():
    samples = {"bench.decode_step": [{"t": 10.0 + i} for i in range(5)],
               "bench.prefill_step": [{"t": 10.5 + i} for i in range(3)]}
    spans = {k: [(s["t"] + OFFSET, s["t"] + OFFSET + 0.01) for s in v]
             for k, v in samples.items()}
    off, spread = latency.clock_offset(samples, spans)
    assert off == pytest.approx(OFFSET, abs=1e-9) and spread < 1e-9
    # one span 0.3 ms off: the clocks do not agree to 0.2 ms, refuse
    bent = dict(spans)
    bent["bench.decode_step"] = ([(s + 0.0003, e) for s, e in
                                  spans["bench.decode_step"][:1]]
                                 + spans["bench.decode_step"][1:])
    assert latency.clock_offset(samples, bent) is None
    # the profiler stopped with a step in flight: its sample has no span
    # (first or last), and the rest still lie against each other
    for cut in (slice(1, None), slice(0, -1)):
        short = dict(spans)
        short["bench.decode_step"] = spans["bench.decode_step"][cut]
        off, _ = latency.clock_offset(samples, short)
        assert off == pytest.approx(OFFSET, abs=1e-9)
    # spans nobody stamped cannot be laid against anything: unequal
    # counts that no shift explains read as nothing
    more = dict(spans)
    more["bench.prefill_step"] = spans["bench.prefill_step"] + [(99.0, 99.1)]
    assert latency.clock_offset(samples, more) is None
    assert latency.clock_offset({}, {}) is None


def test_launch_fetch_and_turnaround_latencies(ring):
    prof, clock = ring
    plan = [("decode", {}), ("prefill", dict(dispatch=0.004)),
            ("decode", {}), ("decode", dict(gap=0.002)),
            ("prefill", dict(dispatch=0.004))]
    run, recs = traced(prof, clock, plan)

    def read(**kw):
        return latency.read(run, **kw)
    dec = ["decode", "verify"]
    assert read(what="launch", kinds=dec) == pytest.approx(0.5)
    assert read(what="fetch", kinds=dec) == pytest.approx(0.3)
    assert read(what="launch", kinds=["prefill"]) == pytest.approx(0.5)
    # emit 0.5 + gap 1 (once 2) + schedule 0.5 + build 1 = 3, once 4 ms
    assert read(what="turnaround") == pytest.approx(3.0)
    assert read(what="turnaround", stat="mean") == pytest.approx(3.25)
    assert read(what="idle_explained") == pytest.approx(100.0)
    # a launch cut by the trace's edge is left out: move the window's
    # start past the first launch's dispatch and its 0.5 ms goes with it
    run.trace.devices[0].ops[0] = ("%fusion.0", 0.0, 0.0)
    run.trace = Reduced((recs[0].phase_start("dispatch") + OFFSET + 0.001,
                         run.trace.window[1]), run.trace.devices,
                        run.trace.annotations)
    rows = latency.laid(run, dec)
    assert [r.seq for r, _, _ in rows] == [recs[2].seq, recs[3].seq]
    # no trace, no ring, no offset: nothing, and no exception
    assert latency.read(made_up_run(), what="launch") is None
    run.step_samples = {}
    del run.setup_parts["clock_offset_s"]       # joined once a run
    assert read(what="launch", kinds=dec) is None


def test_idle_explained_is_100_on_a_tiling_and_lower_with_a_hole(ring):
    prof, clock = ring
    plan = [("decode", {}), ("decode", {}),
            # the engine waited 40 ms for work: no span covers that
            ("decode", dict(idle_before=True, gap=0.040)),
            ("decode", {})]
    run, recs = traced(prof, clock, plan)
    # idle: 0.8 ms inside each of 4 launches, 3 ms in each of 2 busy
    # turnarounds, and 42 ms (gap + emit + schedule + build) in the hole
    explained = 4 * 0.8 + 2 * 3.0
    assert latency.read(run, what="idle_explained") == pytest.approx(
        100.0 * explained / (explained + 42.0))


# -- request_phase: request spans joined to their launches -------------------

def test_ttft_splits_into_own_prefill_and_interleave(ring):
    prof, clock = ring
    opened = clock.t
    spans = []
    for rid, chunks in ((7, 2), (8, 3)):
        submit = clock.t
        clock.t += 0.010                        # queued
        admit = clock.t
        for _ in range(chunks):
            launch(prof, clock, "prefill", request=rid)      # own: 12 ms
            launch(prof, clock, "decode")                    # others'
        first = clock.t
        spans.append(RequestSpan(rid, None, submit, admit, first,
                                 first + 1.0, 100, 10, "length"))
        # a chunk after the first token (a re-prefill) is not TTFT's
        launch(prof, clock, "prefill", request=rid)
    closed = clock.t
    spans.append(RequestSpan(9, None, closed + 1.0, closed + 1.1,
                             closed + 1.2, closed + 2.0, 5, 5, "length"))
    spans.append(RequestSpan(10, None, opened + 0.001, None, None,
                             opened + 0.5, 5, 0, "deadline"))
    for s in spans:
        prof.record_request(s)
    run = made_up_run(opened, closed)
    rows = request_phase.split(run)
    assert [round(own * 1000, 6) for own, _ in rows] == [24.0, 36.0]
    # a launch is 15 ms in all: (12 own + 3 other phases + 15 other's)
    assert rows[0][1] == pytest.approx(2 * 0.030 - 0.024)
    assert rows[1][1] == pytest.approx(3 * 0.030 - 0.036)
    assert request_phase.read(run, what="prefill_own") == pytest.approx(24.0)
    assert request_phase.read(run, what="interleave",
                              q=100) == pytest.approx(54.0)
    assert request_phase.read(made_up_run(), what="interleave") is None


# -- a program without the spans, and the rehearsal ---------------------------

def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    """The parent of the PR that brought the spans has no registry: every
    new source returns None there and raises nothing."""
    from megatron_llm_tpu.serving import loop_profiler

    monkeypatch.delattr(loop_profiler, "live_profilers")
    assert loop_phase.profiler() is None
    run = made_up_run(0.0, 10.0)
    run.step_samples = {"bench.decode_step": [{"t": 1.0}]}
    run.trace = Reduced((OFFSET, OFFSET + 10.0),
                        [DeviceTrace("/device:TPU:0",
                                     [("%fusion.1", OFFSET + 1.0,
                                       OFFSET + 2.0)])],
                        {"bench.decode_step": [(OFFSET + 1.0, OFFSET + 1.1)]})
    for entry in B["per_layer"]:
        if entry["name"] in NEW_SPAN_METRICS | NEW_TRACE_METRICS:
            body = json.load(open(f"{spec.BENCH_DIR}/layer_metrics/"
                                  f"{entry['name']}.json"))
            read = spec.load_module("sources", body["source"]).read
            assert read(run, **body["params"]) is None, entry["name"]


def test_the_new_metrics_are_declared_as_the_issue_lists_them():
    by_name = {m["name"]: m for m in B["per_layer"]}
    assert NEW_SPAN_METRICS | NEW_TRACE_METRICS <= set(by_name)
    for name in NEW_SPAN_METRICS:
        assert by_name[name]["source"] == "program_span"
    for name in NEW_TRACE_METRICS:
        assert by_name[name]["source"] == "device_trace"
    # additions go to the end of the list: what was there keeps its place
    names = [m["name"] for m in B["per_layer"]]
    assert set(names[-14:]) == NEW_SPAN_METRICS | NEW_TRACE_METRICS


@pytest.mark.parametrize("cell", ["mistral-7b-serve.chat",
                                  "mistral-7b-serve.docqa"])
def test_rehearsal_prints_every_new_span_metric_with_no_number(cell):
    """A rehearsal has no device plane, so the ``device_trace`` metrics
    are absent there as the existing ones are; the ``program_span`` ones
    of the cell are all named, each with a null value."""
    p = _run(["--workload", cell, "--rehearse", "--trace", "1"])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True
    want = {m["name"] for m in B["per_layer"]
            if m["name"] in NEW_SPAN_METRICS and cell in m["workloads"]}
    assert want and want <= set(last["metrics"]), p.stdout[-2000:]
    assert all(last["metrics"][n]["value"] is None for n in want)
    assert not NEW_TRACE_METRICS & set(last["metrics"])
