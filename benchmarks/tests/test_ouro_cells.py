"""The cell PR 63 added, rehearsed on the CPU with its per-layer metrics:
``ouro-2.6b-serve.reason-2k`` prefills in chunks and decodes through a
pool a pass of a stack run four times, and prints the new counter's
metric with no number.  Its entries in ``BENCHMARK.json`` are found BY
NAME, not as the last ones: the next PR's append does not turn this file
red (``PERF.md`` open question 10 has what happened to the others)."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from harness import loop_roofline as cost
from harness import spec, traffic
from test_new_cells import _rehearse, _run

CELL = "ouro-2.6b-serve.reason-2k"
CONFIG = "ouro-2.6b-serve"
NEW = {"loop_decode_roofline", "loop_prefill_roofline",
       "loop_walk_roofline", "loop_layer_runs_per_token"}
# accepted metrics whose readers read this model right
TAKEN = {"kv_pool_copy_busy_pct", "device_unattributed_pct",
         "output_norm_busy_pct", "batch_occupancy", "prefix_hit_pct",
         "serve_device_idle_pct", "serve_peak_hbm_gb", "idle_explained_pct",
         "loop_gap_ms", "prefill_program_ms", "setup_trace_lower_s",
         "launch_stall_pct"}
# and those that would count 12 layer applications where 48 run
LEFT = {"prefill_roofline", "decode_roofline", "paged_walk_d64_roofline"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "layer_types"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_the_cell_rehearses_through_a_pool_a_pass(rehearsed):
    last, lines = rehearsed
    assert not ({"itl_p95_ms", "ttft_p50_ms"} | LEFT) & set(last["metrics"])
    # the counter's metric prints (with no number, on a CPU); the three
    # shares of a roofline need a device's trace and peaks
    assert "loop_layer_runs_per_token" in last["metrics"]
    # the probe's tight comparison is of the ENGINE's own logits over its
    # twelve planes, float32 in a rehearsal
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True
    assert probe["answered_alike"] and probe["tapped_every_row"]
    assert probe["step_token_deficit_worst"] == 0.0
    assert probe["planes"] == 12
    assert probe["loop_layer_runs"] == probe["walks"] > 0
    # the tapped chunks' first rows were computed over ADOPTED pages
    assert probe["prefill_tokens_cached"] >= 16 + 32
    # a rehearsal runs tiny widths: they are not the file's, and say so
    assert probe["differs_from_the_file"] == ["head_dim"]
    small = spec.load_cell(CELL).config["probe"]["rehearsal"]
    assert probe["prefill"]["positions"] == small["prefill_rows"] + 1
    assert probe["decode"]["positions"] == small["answer_tokens"] - 1
    assert probe["prefill"]["worst"] < 1e-4 and probe["decode"]["worst"] < 1e-4


def test_the_cell_rehearses_untraced():
    p = _run(["--workload", CELL, "--rehearse"])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == 5 and last["rehearsal"] and not last["correct"]
    assert last["failed"] == 0 and "serve_tokens_per_s" in last["metrics"]
    assert "setup_s" in last["metrics"]


def test_the_entries_are_found_by_name(cell):
    bench = spec.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG, "reason-2k", 1)
    assert len(work["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/ouro-2.6b-serve.json"
    assert entry["reduced"] == cell.config["reduced"] == REDUCED
    reported = {m["name"]: m for m in bench["per_layer"]
                if CELL in m.get("workloads", ())}
    assert NEW | TAKEN <= set(reported) and not LEFT & set(reported)
    # a saturated closed loop lists no metric that moves a latency
    assert {m["moves"] for m in reported.values()} == {
        "serve_tokens_per_s", "setup_s"}
    for name in NEW:
        m = reported[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s" and "mfu" not in name
    for name in NEW - {"loop_layer_runs_per_token"}:
        assert (reported[name]["unit"], reported[name]["source"]) == (
            "%", "device_trace")
    assert reported["loop_layer_runs_per_token"]["source"] == (
        "program_counter")
    ends = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", (CELL,))}
    assert ends == {"serve_tokens_per_s", "setup_s"}
    # one cell in four may take four chips: this one takes one
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_new_metrics_read_the_records_the_scopes_and_the_kernel(cell):
    """The shares by the launch records' fields and the walk's kernel
    names; the counter by ``LOOP_FIELDS``; the scopes are ones the
    program opens and its instruction tables know."""
    import inspect

    from harness.trace import op_family
    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.models import transformer
    from megatron_llm_tpu.ops.pallas import paged_attention
    from megatron_llm_tpu.serving import loop_profiler

    by_name = {m.name: m for m in cell.per_layer}
    assert NEW <= set(by_name)
    ratio = by_name["loop_layer_runs_per_token"]
    assert ratio.source == "loop_record_ratio"
    assert ratio.params["numerator"] in loop_profiler.LOOP_FIELDS
    assert ratio.params["numerator"] in loop_profiler.COUNTED_FIELDS
    assert hasattr(loop_profiler.DispatchRecord, ratio.params["denominator"])
    import re

    walk = by_name["loop_walk_roofline"]
    assert walk.source == "loop_roofline_share"
    source = inspect.getsource(paged_attention)
    for kernel in ("paged_attention_decode", "paged_attention_prefill"):
        assert f'"{kernel}"' in source
        assert re.search(walk.params["pattern"], op_family(kernel + ".3"))
    assert not re.search(walk.params["pattern"],
                         op_family("mla_attention_decode"))
    for name, annotation in (("loop_decode_roofline", "bench.decode_step"),
                             ("loop_prefill_roofline", "bench.prefill_step")):
        assert by_name[name].params["annotation"] == annotation
    stack = inspect.getsource(transformer.transformer_stack)
    for scope in ("loop_pass", "loop_pass_norm"):
        assert f'"{scope}"' in stack and scope in hlo_collectives.SCOPES


def test_the_rooflines_arithmetic_is_the_issues(cell):
    """What no implementation could avoid, at the published widths: a
    plane 8,192 B, a token 393,216 B over twelve layers and four passes;
    the layers' weights once a PASS, the head once."""
    cfg = cell.config
    assert cost.passes(cfg) == 4 and cost.planes(cfg) == 48
    assert cost.plane_bytes(cfg) == 8192
    assert cost.token_bytes(cfg) == 393216 == 12 * 32768
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    head = 49152 * 2048
    # one row of 1,000 live tokens: 48 x 8,192 x 1,001 B of walk
    rec = types.SimpleNamespace(kind="decode", rows=1, context_tokens=1000)
    assert cost.walk_bytes(cfg, cost.launch_tokens(rec)) == 48 * 8192 * 1001
    assert cost.walk_least_seconds(cfg, rec, PEAKS) == pytest.approx(
        48 * 8192 * 1001 / 819e9)
    flops, nbytes = cost.decode_cost(cfg, 1, 1000)
    assert nbytes == 2 * (48 * layer + head) + 1001 * 393216
    assert flops == 2.0 * (48 * layer + head) + 4.0 * 16 * 128 * 48 * 1001
    # the bytes bind a decode step: 4 x 1.23 GB of weights are 6.0 ms
    least = cost.launch_least_seconds(cfg, rec, PEAKS)
    assert least == pytest.approx(nbytes / 819e9)
    assert 2 * 48 * layer / 819e9 == pytest.approx(6.02e-3, rel=0.01)
    # ISSUE 63's step: 11 rows of some 860 live tokens, about 10.8 ms
    flops, nbytes = cost.decode_cost(cfg, 11, 11 * 860)
    assert nbytes / 819e9 == pytest.approx(10.8e-3, rel=0.03)
    # a chunk of 512 from an empty context: the operations bind, 12.8 ms
    # of matmuls at the peak; no head but for a last chunk's one row
    flops, nbytes = cost.prefill_cost(cfg, 0, 512)
    pairs = 512 * 513 // 2
    assert flops == 2.0 * 512 * 48 * layer + 4.0 * 16 * 128 * 48 * pairs
    assert nbytes == 2 * 48 * layer + 512 * 393216
    assert flops / 197e12 == pytest.approx(12.8e-3, rel=0.03)
    chunk = types.SimpleNamespace(kind="prefill", start=0, valid=512,
                                  prefill_head_rows=0)
    assert cost.launch_least_seconds(cfg, chunk, PEAKS) == pytest.approx(
        flops / 197e12)
    last = cost.prefill_cost(cfg, 512, 100, head_rows=1)
    assert last[1] - cost.prefill_cost(cfg, 512, 100)[1] == 2 * head
    assert last[0] - cost.prefill_cost(cfg, 512, 100)[0] == 2.0 * head
    # a model run once counts as harness/roofline.py does, a layer once
    once = {**cfg, "total_ut_steps": 1}
    assert cost.planes(once) == 12 and cost.token_bytes(once) == 98304


def test_the_roofline_share_reads_nothing_where_there_is_nothing(
        monkeypatch):
    """The source on runs that lack what it reads (no trace, records
    without the field: the parent's, a model that loops nothing): None,
    never an error, so the parent's line leaves the metric out."""
    share = spec.load_module("sources", "loop_roofline_share")
    run = types.SimpleNamespace(trace=None, peaks=PEAKS,
                                setup_parts={"traced": (0.0, 1.0)})
    assert share.read(run, "decode", annotation="bench.decode_step") is None
    run.trace = types.SimpleNamespace(op_seconds=lambda pattern: 1.0,
                                      under_annotation=lambda name: [])
    run.setup_parts = {}
    assert share.read(run, "walk", pattern="^paged") is None
    bare = [types.SimpleNamespace(kind="decode", rows=2, context_tokens=9)]
    assert not share.looped(bare) and not share.looped([])
    once = [types.SimpleNamespace(kind="decode", rows=2, context_tokens=9,
                                  loop_layer_runs=0)]
    assert not share.looped(once)
    rec = types.SimpleNamespace(kind="decode", rows=2, context_tokens=998,
                                loop_layer_runs=96, begin=0.5)
    assert share.looped([rec])
    ouro = types.SimpleNamespace(
        cell=spec.load_cell(CELL), model_shape={}, peaks=PEAKS,
        setup_parts={"traced": (0.0, 1.0)},
        trace=types.SimpleNamespace(
            op_seconds=lambda pattern: 0.001,
            under_annotation=lambda name: [0.02] * 8))
    monkeypatch.setattr(share._loop, "launches",
                        lambda t0, t1, kinds=None: [rec])
    assert share.read(ouro, "walk", pattern="^paged") == pytest.approx(
        100 * 1000 * 393216 / 819e9 / 0.001)
    least = cost.launch_least_seconds(ouro.cell.config, rec, PEAKS)
    assert share.read(ouro, "decode", annotation="bench.decode_step") == (
        pytest.approx(100 * least / 0.02))
    # another family's configuration: no ``total_ut_steps``, nothing read
    ouro.cell = spec.load_cell("mistral-7b-serve.chat")
    assert share.read(ouro, "walk", pattern="^paged") is None


def test_the_file_is_the_catalogs_row_but_for_its_two_cuts(cell):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"name": "Ouro-2.6B"' in ln)
    cfg = cell.config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "layer_types": ["full_attention"] * 48}
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 12 >= 4
    assert cfg["layer_types"] == ["full_attention"] * 12
    assert (cfg["max_window_layers"], cfg["use_sliding_window"],
            cfg["total_ut_steps"], cfg["early_exit_threshold"]) == (
                48, False, 4, 1)
    for key in ("deployment", "bytes", "assumed", "published"):
        assert cfg[key], key
    recollected = ("as modeling_ouro.py computes it to my recollection; the "
                   "file is not in the repository")
    for item in ("four_norms", "final_norm_inside_the_loop", "cache_index",
                 "exit_gate", "logits_of_the_last_pass", "rotary_on_halves",
                 "plain_w_in_the_norms"):
        assert cfg["assumed"][item].endswith(recollected), item
    assert cfg["bytes"]["kv_bytes_a_token"] == cost.token_bytes(cfg)
    assert cfg["bytes"]["parameters"] == 817_991_681
    # every tolerance stands beside its readings
    assert "sound" in cfg["probe"]["margin_reason"]


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration(cell):
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"]) == (
        "closed_loop", 12, 0)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 128,
                                  "max": 1024}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 256,
                                  "max": 2048}
    assert (t["open_after_answers"], t["documents_per_cycle"],
            t["strata_requests"], t["trace_seconds"], t["drain_seconds"],
            t["answer_timeout_seconds"]) == (6, 96, 12, 3, 120, 600)
    assert "repeats" not in t
    others = {spec.load_cell(w["name"]).traffic.get("order_seed")
              for w in spec.load_benchmark()["workloads"]
              if w["name"] != CELL}
    assert t["order_seed"] not in others
    src = traffic.ClosedLoopSource(t, 1, 49152)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    prompts = np.array([len(d.prompt) for d in docs])
    answers = np.array([d.answer_tokens for d in docs])
    assert 420 < prompts.mean() < 440 and 850 < answers.mean() < 875
    assert 128 <= prompts.min() and prompts.max() <= 1024
    # the first twelve, one a caller: what their order has to do is held
    # by test_the_window_does_not_close_on_a_prompt
    assert prompts[:12].tolist() == [248, 496, 589, 991, 350, 701, 208,
                                     295, 175, 147, 834, 417]
    longest = 1024 + 2048
    assert max(len(d.prompt) + d.answer_tokens for d in docs) <= longest
    assert "--serve_max_model_len=3584" in flags and longest + 512 == 3584
    assert max(max(d.prompt) for d in docs) < 49152
    # 897 - 1 pages hold at least four requests of the longest
    blocks = int(next(f for f in flags if f.startswith(
        "--serve_num_blocks=")).split("=")[1])
    assert blocks == 897 and (blocks - 1) * 16 >= 4 * longest
    # and eleven of the mean: the pool bounds the batch under the slots
    assert (blocks - 1) * 16 // 1290 == 11 < t["callers"] == 12
    for flag in ("--model_name=ouro", "--num_layers=12", "--kv_channels=128",
                 "--sublayer_output_norm", "--rope_theta=1000000",
                 "--layernorm_epsilon=1e-06", "--vocab_size=49151",
                 "--max_position_embeddings=65536", "--bf16",
                 "--serve_num_slots=12", "--serve_block_size=16",
                 "--serve_prefill_chunk=512", "--serve_preemption=0",
                 "--serve_paged_kernel=auto", "--serve_prefill_kernel=auto"):
        assert flag in flags, flag
    # the flags carry the file's widths, and the passes
    cfg = cell.config
    for key, flag in (("hidden_size", "hidden_size"),
                      ("intermediate_size", "ffn_hidden_size"),
                      ("num_attention_heads", "num_attention_heads"),
                      ("num_key_value_heads", "num_attention_heads_kv"),
                      ("head_dim", "kv_channels"),
                      ("num_hidden_layers", "num_layers"),
                      ("total_ut_steps", "loop_steps")):
        assert f"--{flag}={cfg[key]}" in flags, key
    # the probe: three chunks of 512 and sixteen answer tokens
    assert cfg["probe"]["prompt_tokens"] == 1536 == 3 * 512
    assert (cfg["probe"]["answer_tokens"], cfg["probe"]["prefill_rows"]) == (
        16, 2)
    small = cfg["program"]["rehearsal_flags"]
    for flag in ("--loop_steps=4", "--serve_prefill_chunk=16",
                 "--num_layers=3", "--sublayer_output_norm"):
        assert flag in small, flag
    assert t["rehearsal"]["prompt_tokens"] == {"dist": "loguniform",
                                               "min": 24, "max": 64}
    assert t["rehearsal"]["answer_tokens"] == {"dist": "loguniform",
                                               "min": 8, "max": 16}


def _schedule(docs, pages, slots, chunk, first=None):
    """The engine's schedule of the closed loop, on the chip's clock as
    PR 63's launch records give it (a step 11.06 ms + 7.9 us a row +
    0.478 us a context token, a chunk 41.9 ms, by least squares over
    5,851 steps and 82 chunks: every chunk of a real window within
    0.08 s): head-of-line admission against the pool, a chunk and a
    step in turn, a caller's next document when its last is answered.
    Returns ``(seconds since the window opened, tokens counted, is a
    chunk)`` a launch, the window opening at the sixth answer."""
    order = list(first or range(12)) + list(range(12, len(docs)))
    queue, active, free, t = [list(docs[i]) for i in order[:12]], [], pages, 0.0
    handed, answers, opened, events, was_chunk = 12, 0, None, [], False
    while opened is None or t < opened + 56:
        while queue and len(active) < slots:
            need = -(-(queue[0][0] + queue[0][1]) // 16)
            if need > free:
                break
            free -= need
            p, a = queue.pop(0)
            active.append({"p": p, "a": a, "need": need, "pos": 0, "out": 0})
        pre = [r for r in active if r["pos"] < r["p"]]
        dec = [r for r in active if r["pos"] >= r["p"]]
        was_chunk = bool(pre) and not (dec and was_chunk)
        if was_chunk:
            r = pre[0]
            tokens = min(chunk, r["p"] - r["pos"])
            r["pos"] += tokens
            r["out"] = int(r["pos"] >= r["p"])
            t += 41.9e-3
        else:
            tokens = len(dec)
            t += (11.06e-3 + 7.9e-6 * tokens
                  + 0.4776e-6 * sum(r["p"] + r["out"] for r in dec))
            for r in dec:
                r["out"] += 1
        events.append((t, tokens, was_chunk))
        for r in [r for r in active if r["out"] >= r["a"]]:
            active.remove(r)
            free += r["need"]
            answers += 1
            if answers == 6:
                opened = t
            queue.append(list(docs[order[handed]]))
            handed += 1
    return [(at - opened, n, c) for at, n, c in events if at > opened]


def test_the_window_does_not_close_on_a_prompt(cell):
    """What the ``order_seed`` was chosen for (PERF.md section 6, PR 63):
    a machine ``s`` times as fast as the chip of the records counts the
    tokens of the schedule's first ``45 s`` seconds, so a chunk beside
    the window's close turns 1% of speed into 2-3% of tokens/s.  The
    driver's machine read 0.99."""
    t = cell.traffic
    src = traffic.ClosedLoopSource(t, 1, 49152)
    docs = [(len(d.prompt), d.answer_tokens)
            for d in (src.next() for _ in range(2 * 96))]

    def counted(events, s):
        return sum(n for at, n, _ in events if at <= 45.0 * s) / 45.0

    events = _schedule(docs, 896, t["callers"], 512)
    chunks = np.array([at for at, _, c in events if c])
    close = 45.0 * 0.99
    assert not ((chunks > close - 1.4) & (chunks < close + 1.1)).any()
    rise = (counted(events, 0.99 * 1.015) - counted(events, 0.99 * 0.985)) \
        / counted(events, 0.99)
    assert 0.012 < rise < 0.018           # 34603, the first order: 0.035
    # a traced line needs a chunk inside its 3 s, at any speed near
    for s in 0.99 * np.arange(0.955, 1.0451, 0.005):
        assert ((chunks > 45.1 * s + 0.045) & (chunks < 48.0 * s)).any(), s
    # the first twelve submits race (open question 37a): whichever two
    # neighbours change places, the window counts the same
    for k in range(11):
        first = list(range(12))
        first[k], first[k + 1] = first[k + 1], first[k]
        swapped = _schedule(docs, 896, t["callers"], 512, first)
        assert abs(counted(swapped, 0.99) / counted(events, 0.99) - 1) \
            < 2e-3, k


@pytest.mark.parametrize("control", [
    "shared_planes", "previous_plane", "three_passes", "norm_once",
    "no_output_norms", "float8_activations"])
def test_a_fault_in_the_programs_place_fails_the_probe(control):
    """``ouro_controls.py --control`` plants a fault in the program and
    runs the cell through the harness (rehearsed: float32, tiny): the
    probe's comparison of the ENGINE's logits reads it beyond a limit of
    the configuration file and the run's checks say so.  ``theta_1e4``
    is read on the chip alone: a tiny model's scores are too small
    (weights drawn at 0.02 over a width of 128) for forty positions'
    rotation to show."""
    root = os.path.dirname(spec.BENCH_DIR)
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "ouro_controls.py"),
         "--control", control, "--", "--workload", CELL, "--seed", "7",
         "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[0] == {"note": "control", "planted": control}, p.stderr[-2000:]
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is False
    worst = max(probe["prefill"]["worst"], probe["decode"]["worst"])
    assert worst > probe["position_tolerance"], probe
    checks = next(ln for ln in lines if ln.get("note") == "checks")
    assert checks["probe_within_margin_of_reference"] is False
    assert lines[-1]["correct"] is False
