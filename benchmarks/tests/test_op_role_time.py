"""``sources/op_role_time``: the traced stretch's operations looked up in
the programs' own instruction tables, on a made-up trace, a scripted
launch ring and two small tables."""
import json
import os

import pytest

from conftest import BENCH, ROOT  # noqa: F401 - puts the repo on sys.path
from harness import spec
from harness.trace import DeviceTrace, Reduced
from megatron_llm_tpu import hlo_collectives as H
from megatron_llm_tpu.serving import loop_profiler
from megatron_llm_tpu.serving.loop_profiler import LoopProfiler
from test_loop_sources import OFFSET, Clock, launch, made_up_run

loop_phase = spec.load_module("sources", "loop_phase")
roles = spec.load_module("sources", "op_role_time")

DECODE = """
ENTRY %main.1 (k_pages: bf16[9,4,2,8], x: f32[8,16]) -> (f32[8,16], bf16[9,4,2,8]) {
  %k_pages = bf16[9,4,2,8]{3,2,1,0} parameter(0)
  %x = f32[8,16]{1,0} parameter(1)
  %copy.2 = bf16[9,4,2,8]{3,2,1,0} copy(%k_pages)
  %fusion.1 = f32[8,16]{1,0} multiply(%x, %x), metadata={op_name="jit(engine_decode)/attention/mul"}
  %fusion.3 = f32[8,16]{1,0} add(%fusion.1, %x), metadata={op_name="jit(engine_decode)/sampler/add"}
  %fusion.4 = f32[8,16]{1,0} add(%fusion.3, %x), metadata={op_name="jit(engine_decode)/add"}
  ROOT %tuple.1 = (f32[8,16]{1,0}, bf16[9,4,2,8]{3,2,1,0}) tuple(%fusion.4, %copy.2)
}
"""
PREFILL = """
ENTRY %main.2 (k_pages: bf16[9,4,2,8], x: f32[8,16]) -> (f32[8,16], bf16[9,4,2,8]) {
  %k_pages = bf16[9,4,2,8]{3,2,1,0} parameter(0)
  %x = f32[8,16]{1,0} parameter(1)
  %copy.7 = bf16[9,4,2,8]{3,2,1,0} copy(%k_pages)
  %convolution.9 = f32[8,16]{1,0} multiply(%x, %x), metadata={op_name="jit(engine_prefill)/mlp/moe_route/dot_general"}
  ROOT %tuple.2 = (f32[8,16]{1,0}, bf16[9,4,2,8]{3,2,1,0}) tuple(%convolution.9, %copy.7)
}
"""
COW = """
ENTRY %main.3 (k_pages: bf16[9,4,2,8]) -> bf16[9,4,2,8] {
  %k_pages = bf16[9,4,2,8]{3,2,1,0} parameter(0)
  ROOT %dynamic-update-slice.5 = bf16[9,4,2,8]{3,2,1,0} dynamic-update-slice(%k_pages, %k_pages, %k_pages)
}
"""
POOL = [("bfloat16", (9, 4, 2, 8))]
MS = 0.001


def tables():
    return {name: H.ProgramTable(name, H.instructions(text), pool=POOL)
            for name, text in (("engine_decode", DECODE),
                               ("engine_prefill", PREFILL),
                               ("engine_cow_copy", COW))}


@pytest.fixture
def served(monkeypatch):
    """Three launches (decode, prefill, decode) and the device's
    operations of each, on a clock ``OFFSET`` away from the ring's."""
    clock = Clock()
    prof = LoopProfiler(clock=clock)
    prof.programs = tables()
    monkeypatch.setattr(loop_phase, "profiler", lambda: prof)
    recs = [launch(prof, clock, kind) for kind in
            ("decode", "prefill", "decode")]
    samples, spans = {}, {}
    for d in recs:
        name = ("bench.prefill_step" if d.kind == "prefill"
                else "bench.decode_step")
        ds = d.phase_start("dispatch")
        samples.setdefault(name, []).append({"t": ds})
        spans.setdefault(name, []).append((ds + OFFSET, ds + OFFSET + 0.002))

    def at(d, a, b):
        ds = d.phase_start("dispatch") + OFFSET
        return ds + a * MS, ds + b * MS
    first, second, third = recs
    ops = [
        ("%copy.2", *at(first, 1, 4)),              # 3 ms, kv_pool
        ("%fusion.1", *at(first, 4, 6)),            # 2 ms, attention
        ("%fusion.3", *at(first, 6, 7)),            # 1 ms, sampler
        # the clocks' skew: this one starts 0.2 ms INSIDE the prefill
        # launch's dispatch..fetch, and is the decode program's all the same
        ("%fusion.4", *at(second, 0.2, 1.2)),       # 1 ms, no role
        ("%copy.7", *at(second, 2, 4)),             # 2 ms, kv_pool
        ("%convolution.9", *at(second, 4, 8)),      # 4 ms, moe_route
        # a page program, while the third launch's inputs are built
        ("%dynamic-update-slice.5", *at(third, -1.5, -0.5)),    # 1 ms
        ("%copy.2", *at(third, 1, 4)),              # 3 ms
        ("%fusion.99", *at(third, 4, 6)),           # 2 ms, in no table
        ("%conditional.1", *at(third, 1, 6)),       # encloses the two
    ]
    window = (first.phase_start("dispatch") + OFFSET,
              third.phase_end("fetch") + OFFSET)
    run = made_up_run()
    run.step_samples = samples
    run.trace = Reduced(window, [DeviceTrace("/device:TPU:0", ops)], spans)
    return run, recs


BUSY = 3 + 2 + 1 + 1 + 2 + 4 + 1 + 3 + 2        # ms, containers left out


def test_an_operation_lands_in_its_own_program_across_a_skewed_edge(
        served, capsys):
    run, recs = served
    found = roles.attribute(run)
    how = {(name, i): h for name, _, _, _, i, h in found["devices"][0]}
    # the launch that holds its start, where its program knows the name
    assert how[("%copy.2", 0)] == "own" and how[("%copy.7", 1)] == "own"
    # 0.2 ms inside the prefill launch, in the decode program's table
    assert how[("%fusion.4", 0)] == "neighbour"
    # before the third launch's dispatch, in a page program's table
    assert how[("%dynamic-update-slice.5", 2)] == "page"
    assert how[("%fusion.99", 2)] is None
    note = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if '"op_roles"' in ln][-1]
    assert (note["operations"], note["neighbour"], note["page"],
            note["in_no_table"]) == (9, 1, 1, 1)
    assert note["programs"] == ["engine_cow_copy", "engine_decode",
                                "engine_prefill"]
    worst = note["largest_unattributed"]
    assert [w["name"] for w in worst] == ["fusion.99", "fusion.4"]
    assert worst[1]["opcode"] == "add" and worst[1]["shape"] == [8, 16]
    # a launch's operations have a parent span: its record
    launches = found["rows"]
    assert [launches[i][0].seq for _, _, _, _, i, _ in found["devices"][0]
            if i is not None][:4] == [recs[0].seq] * 4


def test_shares_by_role_and_scope_sum_to_100_with_the_unattributed(served):
    run, _ = served

    def share(**kw):
        return roles.read(run, what="busy_share", **kw)
    pool = share(role="kv_pool",
                 opcode=["copy", "copy-start", "copy-done", "slice-start",
                         "slice-done"])
    assert pool == pytest.approx(100 * (3 + 2 + 3) / BUSY)
    assert share(role="kv_pool") == pytest.approx(100 * 9 / BUSY)
    assert share(scope="sampler") == pytest.approx(100 * 1 / BUSY)
    routing = share(scope=["moe_route", "moe_dispatch", "moe_combine"])
    assert routing == pytest.approx(100 * 4 / BUSY)
    assert share(scope="attention") == pytest.approx(100 * 2 / BUSY)
    # a name in no table, and a row with no role, are the unattributed
    rest = roles.read(run, what="unattributed_share")
    assert rest == pytest.approx(100 * (2 + 1) / BUSY)
    assert share(role="kv_pool") + share(scope="sampler") + routing + share(
        scope="attention") + rest == pytest.approx(100.0)
    assert share(opcode="add", scope="") == pytest.approx(100 * 1 / BUSY)
    with pytest.raises(ValueError):
        roles.read(run, what="nothing")


def test_a_launchs_device_time_is_the_union_of_its_own_operations(served):
    run, _ = served
    # decode: 1..7 ms and the skewed 1 ms of the first; of the third the
    # page program's 1 ms and 1..6 (an operation no table knows is the
    # launch's that holds it; the container is left out)
    assert roles.read(run, what="per_launch_ms",
                      kinds=["decode", "verify"]) == pytest.approx(
        (7 + 6) / 2)
    assert roles.read(run, what="per_launch_ms",
                      kinds=["prefill"]) == pytest.approx(6.0)
    assert roles.read(run, what="per_launch_ms", kinds=["verify"]) is None


def _trained(monkeypatch, table_rows, ops_by_device, window):
    monkeypatch.setattr(loop_phase, "profiler", lambda: None)
    table = H.ProgramTable("train_step", table_rows,
                           mesh_shape={"dp": 2, "tp": 2})
    monkeypatch.setattr(loop_profiler, "live_programs",
                        lambda: {"train_step": table})
    run = made_up_run()
    run.trace = Reduced(window, [DeviceTrace(f"/device:TPU:{i}", ops)
                                 for i, ops in enumerate(ops_by_device)], {})
    return run


STEP = """
ENTRY %main.4 (x: f32[8,16]) -> f32[8,16] {
  %x = f32[8,16]{1,0} parameter(0)
  %all-reduce.1 = f32[8,16]{1,0} all-reduce(%x), replica_groups=FIRST, to_apply=%add
  %fusion.1 = f32[8,16]{1,0} add(%all-reduce.1, %x), metadata={op_name="jit(train_step)/transformer_layer/mlp/add"}
  %psum.7 = f32[8,16]{1,0} all-reduce(%fusion.1), replica_groups={{0,2},{1,3}}, to_apply=%add
  %all-gather.2 = f32[16,16]{1,0} all-gather(%psum.7), replica_groups=SECOND, dimensions={0}
  ROOT %fusion.2 = f32[8,16]{1,0} add(%fusion.1, %x)
}
"""
DP, TP = "{{0,2},{1,3}}", "{{0,1},{2,3}}"


def step_rows(first, second):
    return H.instructions(STEP.replace("FIRST", first).replace("SECOND",
                                                               second))


OPS = [[("%all-reduce.1", 0.0, 1.0), ("%fusion.1", 0.5, 1.5),
        ("%all-gather.2", 2.0, 3.0), ("%fusion.2", 3.0, 3.5)],
       [("%all-reduce.1", 0.0, 2.0), ("%fusion.1", 0.5, 1.0),
        ("%all-gather.2", 2.5, 3.0), ("%fusion.2", 3.0, 3.5)]]


def test_exposed_by_edge_is_the_harness_own_when_one_edge_holds_all(
        monkeypatch):
    run = _trained(monkeypatch, step_rows(DP, DP), OPS, (0.0, 4.0))
    whole = 100 * max(
        run.trace.collective_exposed_by_device().values()) / 4.0
    assert whole == pytest.approx(100 * (1.0 + 0.5 + 0.5) / 4.0)
    assert roles.read(run, what="exposed_share", edge="dp") == \
        pytest.approx(whole)
    assert roles.read(run, what="exposed_share", edge="tp") == 0.0
    # the scoped fusion and the collectives are known; the last add is not
    assert roles.read(run, what="unattributed_share") == pytest.approx(
        100 * 1.0 / (3.0 + 3.0))
    assert roles.read(run, what="per_launch_ms") is None


def test_exposed_by_edge_splits_the_edges_and_counts_a_psum_under_its(
        monkeypatch):
    ops = [dev + [("%psum.7", 3.5, 3.75)] for dev in OPS]
    run = _trained(monkeypatch, step_rows(DP, TP), ops, (0.0, 4.0))
    dp = roles.read(run, what="exposed_share", edge="dp")
    tp = roles.read(run, what="exposed_share", edge="tp")
    # device 1: the all-reduce alone for 0.5 + 1.0 s and the psum, which
    # the trace's family pattern does not know, for 0.25
    assert dp == pytest.approx(100 * 1.75 / 4.0)
    assert tp == pytest.approx(100 * 1.0 / 4.0)
    harness = 100 * max(
        run.trace.collective_exposed_by_device().values()) / 4.0
    assert dp + tp >= harness


def test_a_program_that_publishes_no_table_reads_as_nothing(monkeypatch):
    class Parent:                  # the parent's profiler: a ring, no tables
        def launches(self):
            return 3

    monkeypatch.setattr(loop_phase, "profiler", lambda: Parent())
    monkeypatch.setattr(loop_profiler, "live_programs", lambda: {})
    run = made_up_run()
    run.trace = Reduced((0.0, 1.0), [DeviceTrace("/device:TPU:0",
                                                 [("%copy.1", 0.0, 0.5)])],
                        {})
    assert roles.read(run, what="busy_share", role="kv_pool") is None
    assert roles.read(run, what="unattributed_share") is None
    assert roles.read(made_up_run(), what="unattributed_share") is None


NEW = {
    "kv_pool_copy_busy_pct.chat": "itl_p95_ms",
    "kv_pool_copy_busy_pct": "serve_tokens_per_s",
    "sampler_busy_pct.chat": "itl_p95_ms",
    "moe_routing_busy_pct": "serve_tokens_per_s",
    "decode_launch_device_ms.chat": "itl_p95_ms",
    "prefill_launch_device_ms": "serve_tokens_per_s",
    "device_unattributed_pct": "serve_tokens_per_s",
    "train_device_unattributed_pct": "train_tokens_per_s",
    "train_dp_collective_exposed_pct": "train_tokens_per_s",
    "train_tp_collective_exposed_pct": "train_tokens_per_s",
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metrics_that_read_the_tables_are_declared(name):
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        body = json.load(f)
    assert body["source"] == "op_role_time"
    assert entry["source"] == "device_trace"
    assert entry["moves"] == body["moves"] == NEW[name]
    assert entry["workloads"] == body["cells"]
    cells = {w["name"]: w for w in bench["workloads"]}
    reported = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    for cell in entry["workloads"]:
        assert cell in cells
        listed = reported[NEW[name]]
        assert listed is None or cell in listed, (cell, NEW[name])
    # each is read by the cell's own loader
    metric = next(m for m in spec.load_cell(entry["workloads"][0]).per_layer
                  if m.name == name)
    assert metric.source == "op_role_time" and "what" in metric.params
