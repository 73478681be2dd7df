"""The cell PR 47 added, rehearsed on the CPU with its per-layer metrics:
``agent-16k`` prefills in chunks and decodes LONGER than it prefills over
a two-group pool, through gated attention and four norms a layer, two
leading dense layers inside the typed stack and a share of the router's
experts held; the configuration file against the catalog's row; and the
new roofline's arithmetic on made-up records."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from harness import moe_gated_held_roofline, spec, traffic
from harness.context import Run
from test_new_cells import _rehearse, _run

CELL = "trinity-mini-serve.agent-16k"
MELLUM = "mellum2-12b-a2.5b-serve.repo-mixed"
NEW = ["attn_gate_busy_pct", "output_norm_busy_pct",
       "moe_gated_held_roofline"]
REDUCED = ["num_hidden_layers", "layer_types", "num_experts"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

share = spec.load_module("sources", "moe_gated_held_roofline_share")


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


def test_the_cell_rehearses_over_two_groups_with_a_share_held(rehearsed):
    last, lines = rehearsed
    assert not {"itl_p95_ms", "ttft_p50_ms", "moe_roofline",
                "prefill_roofline", "decode_roofline", "mla_busy_pct",
                "ssm_busy_pct", "dsa_busy_pct"} & set(last["metrics"])
    assert {"moe_held_assignments_pct", "kv_window_pages_returned_pct",
            "kv_held_bytes_per_token", "batch_occupancy"} <= set(
                last["metrics"])
    # the probe's tight comparison is of the ENGINE's own logits over its
    # two-group pool; float32 in a rehearsal
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True
    assert probe["answered_alike"] and probe["tapped_every_row"]
    assert probe["step_token_deficit_worst"] == 0.0
    assert probe["window_pages_returned"] > 0
    assert 0 < probe["moe_experts_touched_held"]
    held = probe["moe_assignments_held"] / probe["moe_assignments"]
    assert 0.3 < held < 0.7
    # a rehearsal runs tiny widths: they are not the file's, and say so;
    # what is no width is the file's even there
    differs = set(probe["differs_from_the_file"])
    assert {"head_dim", "moe_intermediate_size", "num_experts"} <= differs
    assert not differs & {"layer_types", "num_dense_layers",
                          "rotating_layer_types", "attention_output_gate",
                          "sublayer_output_norm", "route_scale",
                          "score_func", "mup_enabled", "experts_first"}
    small = spec.load_cell(CELL).config["probe"]["rehearsal"]
    assert probe["prefill"]["positions"] == small["prefill_rows"] + 1
    assert probe["decode"]["positions"] == small["answer_tokens"] - 1
    assert probe["prefill"]["worst"] < 1e-4 and probe["decode"]["worst"] < 1e-4


def test_the_cell_rehearses_untraced():
    p = _run(["--workload", CELL, "--rehearse"])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == 5 and last["rehearsal"] and not last["correct"]
    assert last["failed"] == 0
    assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_file_is_the_catalogs_row_but_for_its_three_cuts():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"name": "Trinity-Mini"' in ln)
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert set(cfg["published"]) == set(REDUCED)
    # the first 8 published layers as they stand: both dense layers, the
    # period twice
    assert cfg["num_hidden_layers"] == 8 == len(cfg["layer_types"])
    assert cfg["layer_types"] == row["config"]["layer_types"][:8] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert cfg["num_dense_layers"] == 2
    assert cfg["num_experts"] == 64 and cfg["experts_first"] == 0
    # every item the row's keys do not say stands with its basis
    for item in ("attention_output_gate", "four_norms", "rotation",
                 "qk_norm", "mup_enabled", "router"):
        assert cfg["assumed"][item].startswith("ASSUMED"), item
    assert "modeling_afmoe.py" in cfg["assumed"]["basis"]
    assert len(entry["why"]) <= 200
    assert "v5e-8" in cfg["deployment"]
    assert "4 pipeline stages" in cfg["deployment"]
    assert "3,568,898,816" in cfg["assumed"]["num_hidden_layers"]
    # every tolerance stands beside its readings
    assert "SOUND" in cfg["probe"]["margin_reason"]


def test_the_flags_carry_the_published_widths():
    cell = spec.load_cell(CELL)
    cfg, flags = cell.config, cell.config["program"]["flags"]
    for flag in ("--model_name=trinity", "--num_layers=8",
                 f"--hidden_size={cfg['hidden_size']}",
                 f"--num_attention_heads={cfg['num_attention_heads']}",
                 f"--num_attention_heads_kv={cfg['num_key_value_heads']}",
                 f"--kv_channels={cfg['head_dim']}",
                 f"--ffn_hidden_size={cfg['intermediate_size']}",
                 f"--moe_ffn_hidden_size={cfg['moe_intermediate_size']}",
                 f"--num_experts={cfg['num_experts']}",
                 f"--moe_router_experts={cfg['published']['num_experts']}",
                 "--moe_experts_first=0",
                 f"--moe_top_k={cfg['num_experts_per_tok']}",
                 f"--moe_first_dense_layers={cfg['num_dense_layers']}",
                 f"--moe_shared_experts={cfg['num_shared_experts']}",
                 "--moe_score_function=" + cfg["score_func"],
                 "--moe_choice_bias=1", "--moe_choice_bias_std=0.02",
                 "--norm_topk_prob=1",
                 f"--moe_routed_scale={cfg['route_scale']}",
                 "--qk_norm_per_head", "--attention_output_gate",
                 "--sublayer_output_norm",
                 f"--embedding_multiplier={cfg['hidden_size'] ** 0.5!r}",
                 f"--sliding_window_size={cfg['sliding_window']}",
                 f"--rope_theta={cfg['rope_theta']}",
                 "--layernorm_epsilon=1e-05",
                 f"--max_position_embeddings={cfg['max_position_embeddings']}",
                 "--bf16", "--vocab_size=200191", "--serve_num_slots=48",
                 "--serve_prefill_chunk=512", "--serve_block_size=16",
                 "--serve_max_model_len=20992", "--serve_preemption=0"):
        assert flag in flags, flag
    at = flags.index("--layer_types")
    assert flags[at + 1:at + 5] == ["sliding", "sliding", "sliding", "full"]
    at = flags.index("--rope_layer_types")
    assert flags[at + 1] == "sliding" and flags[at + 2].startswith("--")
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["sliding_window"],
            cfg["vocab_size"]) == (2048, 6144, 1024, 2048, 200192)
    small = cfg["program"]["rehearsal_flags"]
    for flag in ("--moe_router_experts=8", "--num_experts=4",
                 "--moe_first_dense_layers=2", "--attention_output_gate",
                 "--sublayer_output_norm", "--sliding_window_size=16",
                 "--embedding_multiplier=11.313708498984761"):
        assert flag in small, flag


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration():
    cell = spec.load_cell(CELL)
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"],
            t["trace_seconds"], t["open_after_answers"],
            t["drain_seconds"]) == ("closed_loop", 48, 0, 3, 16, 240)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 1024,
                                  "max": 16384}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 512,
                                  "max": 4096}
    assert (t["order_seed"], t["strata_requests"],
            t["documents_per_cycle"]) == (23, 16, 192)
    src = traffic.ClosedLoopSource(t, 1, 200192)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    assert 5400 < np.mean([len(d.prompt) for d in docs]) < 5700
    assert 1680 < np.mean([d.answer_tokens for d in docs]) < 1760
    assert 3900 < np.median([len(d.prompt) for d in docs]) < 4300
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert "--serve_max_model_len=20992" in flags
    assert longest <= 16384 + 4096 == 20992 - 512
    assert max(max(d.prompt) for d in docs) < 200192
    # the full group holds 48 of the longest requests' MEAN reservation
    blocks = int(next(f for f in flags if f.startswith(
        "--serve_num_blocks=")).split("=")[1])
    assert (blocks - 1) * 16 == 524288
    slots = int(next(f for f in flags if f.startswith(
        "--serve_num_slots=")).split("=")[1])
    assert t["callers"] == slots == 48
    mean = np.mean([len(d.prompt) + d.answer_tokens for d in docs])
    assert 48 * mean < 524288
    # the probe: twelve chunks, the window's pages long gone, >= 16 steps
    p = cell.config["probe"]
    assert p["prompt_tokens"] >= 6144 and p["answer_tokens"] >= 17
    assert p["prefill_rows"] == 8
    bench = spec.load_benchmark()
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ())}
    mellums = {m["name"] for m in bench["per_layer"]
               if MELLUM in m.get("workloads", ())}
    assert reported == mellums | set(NEW) | {"moe_shared_busy_pct",
                                             "moe_held_assignments_pct"}
    assert not reported & {"decode_roofline", "prefill_roofline",
                           "moe_roofline", "moe_ungated_roofline"}
    moves = {m["moves"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert moves == {"serve_tokens_per_s"}
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert served["workloads"][-1] == CELL
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert len(bench["workloads"][-1]["why"]) <= 200
    assert [m["name"] for m in bench["per_layer"][-3:]] == NEW
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"][-3:])
    assert len(bench["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_new_metrics_read_the_new_scopes_and_the_kernels_name():
    import inspect

    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.ops.pallas import grouped_matmul
    from megatron_llm_tpu.serving import loop_profiler

    cell = spec.load_cell(CELL)
    by_name = {m.name: m for m in cell.per_layer}
    roof = by_name["moe_gated_held_roofline"]
    assert roof.source == "moe_gated_held_roofline_share"
    assert roof.params == {"pattern": "^moe_experts"}
    assert 'name="moe_experts"' in inspect.getsource(
        grouped_matmul.grouped_matmul)
    assert "moe_experts_touched_held" in loop_profiler.MOE_FIELDS
    assert by_name["attn_gate_busy_pct"].params == {
        "what": "busy_share", "scope": ["attn_gate"]}
    assert by_name["output_norm_busy_pct"].params == {
        "what": "busy_share", "scope": ["post_attn_norm", "post_mlp_norm"]}
    assert {"attn_gate", "post_attn_norm", "post_mlp_norm"} <= set(
        hlo_collectives.SCOPES)
    for name in NEW:
        body = json.load(open(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".json")))
        assert body["cells"] == [CELL] and body["unit"] == "%"


def test_the_scopes_reach_the_instruction_tables_of_the_engines_programs():
    import jax

    from megatron_llm_tpu.models.trinity import TrinityModel, trinity_config
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    model = TrinityModel(trinity_config("tiny", use_flash_attn=False))
    eng = InferenceEngine(
        model, model.init(jax.random.PRNGKey(0)),
        EngineConfig(num_slots=2, block_size=16, max_model_len=64,
                     prefill_chunk=16))
    eng.warmup()
    tables = eng.program_tables()
    for name in ("engine_prefill", "engine_decode"):
        scopes = {r["scope"] for r in tables[name].rows}
        assert {"post_attn_norm", "post_mlp_norm", "qk_norm", "moe_shared",
                "moe_route", "moe_dispatch", "moe_combine", "kv_write",
                "attention"} <= scopes, (name, scopes)
        # the gate's product is one multiply a layer, which the compiler
        # may fuse into a neighbour whose scope then outvotes it (the
        # CPU's decode step does): it is in the program either way
        assert any("/attn_gate/" in r["op_name"] for r in tables[name].rows)
    assert "attn_gate" in {r["scope"] for r in tables["engine_prefill"].rows}


# ---------------------------------------------------------------------------
# the new roofline on made-up records
# ---------------------------------------------------------------------------

TRINITY = {"hidden_size": 2048, "intermediate_size": 6144,
           "moe_intermediate_size": 1024}
EXPERT = 3 * 2048 * 1024


def _rec(**fields):
    return types.SimpleNamespace(kind="decode", **fields)


def test_a_gated_expert_is_three_matrices_at_the_experts_own_width():
    assert moe_gated_held_roofline.expert_params(TRINITY) == EXPERT == 6291456
    flops, nbytes = moe_gated_held_roofline.expert_matrices_cost(
        TRINITY, 192, 50)
    assert flops == 192 * 6 * 2048 * 1024
    assert nbytes == (50 * EXPERT + 192 * 2 * 2048) * 2
    secs, bound = moe_gated_held_roofline.least_seconds(TRINITY, 192, 50,
                                                        PEAKS)
    # a decode step's layer: 0.63 GB of matrices, bandwidth's
    assert bound == "bandwidth" and secs == pytest.approx(nbytes / 819e9)
    assert 0.7e-3 < secs < 0.8e-3
    assert moe_gated_held_roofline.least_seconds(
        TRINITY, 64 * 2048, 64, PEAKS)[1] == "compute"


def test_the_share_sums_the_records_and_never_reads_above_100():
    recs = [_rec(moe_assignments_held=192, moe_experts_touched_held=50 * 6),
            _rec(moe_assignments_held=0, moe_experts_touched_held=0),
            _rec(moe_assignments_held=2048 * 6, moe_experts_touched_held=384)]
    least = share.least_total(TRINITY, recs, PEAKS)
    assert least == pytest.approx(sum(
        moe_gated_held_roofline.least_seconds(TRINITY, a, e, PEAKS)[0]
        for a, e in ((192, 300), (2048 * 6, 384))))
    # a kernel that reads every touched held expert's three matrices ONCE
    # at the chip's whole bandwidth, its rows in and out, and nothing
    # else takes exactly the least time: 100%, and any real one longer
    ideal = sum((e * EXPERT + a * 2 * 2048) * 2 / 819e9
                for a, e in ((192, 300), (2048 * 6, 384)))
    assert 100.0 * least / ideal == pytest.approx(100.0)
    # counted at the dense layers' width it would read six times higher
    assert moe_gated_held_roofline.expert_params(
        {**TRINITY, "moe_intermediate_size": 6144}) == 6 * EXPERT
    # a record without the field, nothing held, a configuration without
    # the experts' own width: no reading
    assert share.least_total(TRINITY, [_rec(moe_assignments_held=5)],
                             PEAKS) is None
    assert share.least_total(TRINITY, [recs[1]], PEAKS) is None
    run = Run(cell=types.SimpleNamespace(config=dict(TRINITY)), seed=0,
              seconds=10.0, traced=True, rehearsal=False, process_start=0.0)
    run.peaks = PEAKS
    assert share.read(run, "^moe_experts") is None          # no trace


@pytest.mark.parametrize("control", ["no_gate", "dense_layer_sparse"])
def test_a_fault_in_the_programs_place_fails_the_probe(control):
    """``trinity_controls.py --control`` plants a fault in the program and
    runs the cell through the harness (rehearsed: float32, tiny): the
    probe's comparison of the ENGINE's logits reads it beyond a limit of
    the configuration file and the run's checks say so.  (The others are
    the chip's to show: the file's ``probe.margin_reason`` has them.)"""
    root = os.path.dirname(spec.BENCH_DIR)
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "trinity_controls.py"),
         "--control", control, "--", "--workload", CELL, "--seed", "7",
         "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[0] == {"note": "control", "planted": control}
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is False
    checks = next(ln for ln in lines if ln.get("note") == "checks")
    assert checks["probe_within_margin_of_reference"] is False
    assert lines[-1]["correct"] is False
