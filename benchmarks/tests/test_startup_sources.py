"""The two sources that read what the program says of its own set-up
and of a launch's lost time, on made-up timelines and rings:
``startup_span`` (the start-up timeline and the compile ledger, on the
host clock the window is on) and ``launch_stall`` (``compile_secs`` /
``gc_secs`` of the launch records, and a launch's length against its
kind's median)."""
import pytest

from conftest import ROOT  # noqa: F401 - puts the repo on sys.path
from harness import spec
from harness.window import CounterSnapshot, Window
from megatron_llm_tpu.serving.loop_profiler import LoopProfiler
from test_loop_sources import Clock, launch, made_up_run

startup_span = spec.load_module("sources", "startup_span")
launch_stall = spec.load_module("sources", "launch_stall")
loop_phase = spec.load_module("sources", "loop_phase")

B = spec.load_benchmark()
# the thirteen cells there were when the nine metrics came (a later cell
# is appended to a list, or left out of it, by the PR that brings it)
CELLS = [w["name"] for w in B["workloads"]][:13]
SERVING = [c for c in CELLS if "train" not in c]
SETUP_METRICS = {"setup_imports_s": "imports", "setup_build_s": "build",
                 "setup_trace_lower_s": "trace_lower",
                 "setup_cache_load_s": "cache_load",
                 "setup_warm_run_s": "warm_run",
                 "setup_unnamed_pct": "unnamed_pct"}
LAUNCH_METRICS = {"launch_compile_s": "compile_s", "launch_gc_ms": "gc_ms",
                  "launch_stall_pct": "stall_pct"}


def serving_timeline():
    """Process start 0; first stamp 10; ready 40; the window opens at 62
    after 20 s of lead-in: 2 s are nobody's."""
    spans = [("imports", 10.0, 14.0, {}), ("initialize", 14.0, 15.0, {}),
             ("runtime_init", 14.2, 14.8, {}),
             ("build_model", 15.0, 16.0, {}), ("imports", 15.1, 15.6, {}),
             ("init_params", 16.0, 20.0, {}), ("shard_params", 20.0, 21.0, {}),
             ("engine_init", 21.5, 23.5, {}), ("warmup", 24.0, 40.0, {}),
             ("warmup.engine_prefill", 24.0, 30.0, {})]
    events = [
        ("trace", "init", 16.5, 17.5, 1), ("lower", "init", 17.5, 18.0, 1),
        ("backend", "init", 18.0, 19.0, 1),
        # an inner jit inside its caller's trace: a union, not a sum
        ("trace", "kernel", 25.0, 26.0, 1),
        ("trace", "engine_prefill", 24.5, 27.0, 1),
        ("lower", "engine_prefill", 27.0, 28.0, 1),
        # a load from the cache inside its backend event
        ("cache_load", "engine_prefill", 28.1, 28.9, 1),
        ("backend", "engine_prefill", 28.0, 29.0, 1),
        # after the window opened: nobody's set-up
        ("trace", "late", 70.0, 71.0, 1)]
    tl = {"first": 10.0, "ready": 40.0, "spans": spans, "events": events[:-1],
          "summary": {"top_programs": []}}
    return tl, events


def test_the_parts_of_a_serving_setup_add_up():
    tl, events = serving_timeline()
    out, top = startup_span.parts(tl, events, 0.0, 62.0, lead_in_s=20.0)
    assert [s[0] for s in top] == [
        "imports", "initialize", "build_model", "init_params",
        "shard_params", "engine_init", "warmup"]
    assert out["imports"] == pytest.approx(4.0)
    # initialize 1 + build_model 1 + init_params 4 - 2.5 + shard 1 + engine 2
    assert out["build"] == pytest.approx(1 + 1 + (4 - 2.5) + 1 + 2)
    assert out["trace_lower"] == pytest.approx(1.5 + 2.5 + 1.0)
    assert out["cache_load"] == pytest.approx(0.8)
    assert out["backend"] == pytest.approx(2.0)
    assert out["warm_run"] == pytest.approx(16.0 - 4.5)
    # set-up less the lead-in is 42 s: 10 before the first stamp, 29 in
    # top-level spans, 3 in none (0.5 + 0.5 between spans, 2 after ready)
    assert out["before_first_stamp"] == 10.0 and out["lead_in"] == 20.0
    assert out["unnamed_pct"] == pytest.approx(100.0 * 3.0 / 42.0)
    # the parts and the unnamed rest make the whole, on one clock
    named = 10.0 + sum(s[2] - s[1] for s in top)
    assert named + 3.0 + 20.0 == pytest.approx(62.0)
    # union, not sum: never more than set-up less the lead-in
    assert out["trace_lower"] + out["backend"] <= out["setup_less_lead"]


def test_a_training_setup_takes_ready_to_the_window_as_its_lead_in():
    spans = [("imports", 5.0, 8.0, {}), ("initialize", 8.0, 9.0, {}),
             ("build_train_step", 9.0, 9.5, {}), ("first_step", 10.0, 30.0, {})]
    events = [("trace", "train_step", 10.5, 14.5, 1),
              ("backend", "train_step", 15.0, 25.0, 1),
              ("cache_load", "train_step", 15.5, 24.5, 1)]
    tl = {"first": 5.0, "ready": 30.5, "spans": spans, "events": events,
          "summary": {"top_programs": []}}
    out, _ = startup_span.parts(tl, events, 0.0, 36.5)
    assert out["lead_in"] == pytest.approx(6.0)         # warm steps 2 and 3
    assert out["warm_run"] == pytest.approx(20.0 - 14.0)
    assert out["build"] == pytest.approx(1.5)
    assert out["cache_load"] == pytest.approx(9.0)
    assert out["unnamed_pct"] == pytest.approx(100.0 * 1.0 / 30.5)


def test_a_read_prints_the_note_and_a_parent_reads_as_nothing(monkeypatch,
                                                              capsys):
    tl, events = serving_timeline()
    run = made_up_run()
    assert startup_span.read(run, "imports") is None     # no window yet
    run.setup_parts.update(window_opened_at=62.0, lead_in_s=20.0)
    monkeypatch.setattr(startup_span, "timeline", lambda: (tl, events))
    assert startup_span.read(run, "imports") == pytest.approx(4.0)
    assert "setup_timeline" not in capsys.readouterr().out
    assert startup_span.read(run, "unnamed_pct") == pytest.approx(
        100.0 * 3.0 / 42.0)
    said = capsys.readouterr().out
    assert '"note": "setup_timeline"' in said and "warmup.engine_prefill" \
        in said and '"before_first_stamp_s": 10.0' in said
    # a program that keeps no timeline: every part reads as nothing
    monkeypatch.setattr(startup_span, "timeline", lambda: None)
    assert all(startup_span.read(run, p) is None
               for p in SETUP_METRICS.values())


def test_this_programs_tracing_module_has_a_timeline_to_read():
    from megatron_llm_tpu import tracing

    tracing.startup_begin()
    with tracing.startup_span("initialize"):
        pass
    tracing.startup_ready(printer=None)
    tl, events = startup_span.timeline()
    assert tl["spans"][0][0] == "initialize" and tl["ready"] >= tl["first"]
    assert events == tracing.compile_ledger().events


# -- launch_stall -----------------------------------------------------------

@pytest.fixture
def ring(monkeypatch):
    clock = Clock()
    prof = LoopProfiler(clock=clock)
    monkeypatch.setattr(loop_phase, "profiler", lambda: prof)
    return prof, clock


def test_lost_seconds_and_stalls_over_the_window_and_the_stretch(ring,
                                                                 capsys):
    prof, clock = ring
    before = launch(prof, clock, "decode")
    before.compile_secs = 9.0               # warm-up's: began before
    opened = clock.t + 0.0005
    recs = [launch(prof, clock, "decode", dispatch=0.002, fetch=0.010)
            for _ in range(8)]
    recs += [launch(prof, clock, "prefill", dispatch=0.1, fetch=0.1)
             for _ in range(3)]
    recs[2].gc_secs = 0.011
    slow = launch(prof, clock, "decode", dispatch=0.002, fetch=0.098)
    slow.gc_secs = 0.044
    closed = clock.t + 0.0001
    between = launch(prof, clock, "decode", gap=0.0002, fetch=5.0)
    t0 = clock.t + 0.0001
    late = launch(prof, clock, "decode", dispatch=0.002, fetch=0.048)
    late.compile_secs = 0.03
    t1 = clock.t + 0.0001
    after = launch(prof, clock, "decode")
    after.compile_secs = 7.0
    run = made_up_run(opened, closed)
    run.setup_parts["traced"] = (t0, t1)
    assert launch_stall.read(run, "compile_s") == pytest.approx(0.03)
    assert launch_stall.read(run, "gc_ms") == pytest.approx(
        1000 * 0.055 / 12)
    # medians: decode 12 ms, prefill 200 ms; slow is 64 ms over 3 x 12,
    # late 14 ms over; `between` began in neither span
    want = 100.0 * (0.064 + 0.014) / ((closed - opened) + (t1 - t0))
    assert launch_stall.read(run, "stall_pct") == pytest.approx(want)
    said = capsys.readouterr().out
    assert '"note": "launch_stalls"' in said
    assert f'"seq": {slow.seq}' in said and f'"seq": {late.seq}' in said
    assert f'"seq": {between.seq}' not in said
    assert '"gc_secs": 0.044' in said and '"rows": 0' in said
    # untraced: the window alone
    del run.setup_parts["traced"]
    assert launch_stall.read(run, "compile_s") == 0.0
    assert launch_stall.read(run, "stall_pct") == pytest.approx(
        100.0 * 0.064 / (closed - opened))


def test_a_ring_without_the_fields_reads_as_nothing(ring, monkeypatch):
    prof, clock = ring
    opened = clock.t - 0.001
    recs = [launch(prof, clock, "decode") for _ in range(3)]
    run = made_up_run(opened, clock.t + 1.0)
    assert launch_stall.read(run, "compile_s") == 0.0

    class Old:          # a record of the parent: no such attributes
        kind, wait_secs = "decode", 0.01

        def __init__(self, r):
            self.begin = r.begin

    monkeypatch.setattr(loop_phase, "launches",
                        lambda *a, **k: [Old(r) for r in recs])
    assert all(launch_stall.read(run, w) is None
               for w in LAUNCH_METRICS.values())
    assert launch_stall.read(made_up_run(), "gc_ms") is None


# -- the declarations --------------------------------------------------------

def test_the_nine_metrics_are_declared_as_the_issue_lists_them():
    by_name = {m["name"]: m for m in B["per_layer"]}
    names = [m["name"] for m in B["per_layer"]]
    for name, part in SETUP_METRICS.items():
        m = by_name[name]
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "program_span", "entry points", "setup_s", "lower")
        assert m["workloads"][:13] == CELLS and len(CELLS) == 13
        assert m["unit"] == ("%" if name.endswith("_pct") else "s")
        loaded = spec._metric(m, "per_layer", "layer_metrics")
        assert (loaded.source, loaded.params) == ("startup_span",
                                                  {"part": part})
    for name, what in LAUNCH_METRICS.items():
        m = by_name[name]
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "program_counter", "engine loop", "serve_tokens_per_s", "lower")
        assert m["workloads"][:12] == SERVING and len(SERVING) == 12
        loaded = spec._metric(m, "per_layer", "layer_metrics")
        assert (loaded.source, loaded.params) == ("launch_stall",
                                                  {"what": what})
    assert by_name["launch_compile_s"]["unit"] == "s"
    assert by_name["launch_gc_ms"]["unit"] == "ms"
    assert by_name["launch_stall_pct"]["unit"] == "%"
    # appended after what was there, which keeps its place; compile_s stays
    assert names.index("setup_imports_s") > names.index(
        "moe_gated_held_roofline")
    assert "workloads" not in by_name["compile_s"]
