"""The cell PR 44 added, rehearsed on the CPU with its per-layer metrics:
``reason-4k`` prefills in chunks whose state is carried in a slot and
decodes LONGER than it prefills, through layers of one sublayer each
(a share of the router's ungated experts held), and prints the
state-space and the held-experts metrics with no number; and the new
roofline's arithmetic on made-up records."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from harness import moe_ungated_roofline, spec, traffic
from harness.context import Run
from test_new_cells import _rehearse, _run

CELL = "nemotron-3-nano-30b-a3b-serve.reason-4k"
GRANITE = "granite-4.0-h-small-serve.sessions-16k"
NEW = "moe_ungated_roofline"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]
ALIASES = {"mamba_n_heads": "mamba_num_heads", "mamba_d_head":
           "mamba_head_dim", "mamba_d_state": "ssm_state_size",
           "mamba_n_groups": "n_groups", "mamba_d_conv": "conv_kernel"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

share = spec.load_module("sources", "moe_ungated_roofline_share")


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


def test_the_cell_rehearses_through_layers_of_one_sublayer(rehearsed):
    last, lines = rehearsed
    assert not {"itl_p95_ms", "ttft_p50_ms", "moe_roofline",
                "prefill_roofline", "decode_roofline", "mla_busy_pct",
                "dsa_busy_pct"} & set(last["metrics"])
    assert {"moe_held_assignments_pct", "ssm_state_held_gb"} <= set(
        last["metrics"])
    # the probe's tight comparison is of the ENGINE's own logits, the
    # state carried in its slot across every chunk and step; float32 in a
    # rehearsal
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True
    assert probe["answered_alike"] and probe["tapped_every_row"]
    assert probe["step_token_deficit_worst"] == 0.0
    assert probe["ssm_rows_live"] > 0
    assert 0 < probe["moe_experts_touched_held"]
    held = probe["moe_assignments_held"] / probe["moe_assignments"]
    assert 0.3 < held < 0.7
    # a rehearsal runs tiny widths: they are not the file's, and say so
    assert "ssm_state_size" in probe["differs_from_the_file"]
    assert "hybrid_override_pattern" not in probe["differs_from_the_file"]
    small = spec.load_cell(CELL).config["probe"]["rehearsal"]
    # it decodes longer than it taps chunks: every step is compared
    assert probe["prefill"]["positions"] == small["prefill_rows"] + 1
    assert probe["decode"]["positions"] == small["answer_tokens"] - 1
    assert probe["prefill"]["worst"] < 1e-4 and probe["decode"]["worst"] < 1e-4
    state = probe["state"]
    assert state["within"] and state["answered_alike"]
    assert state["layers"] == len(state["head_apart"]) == 6
    assert state["worst"] < 1e-4
    assert state["first_layer_slow_heads_apart"] < 1e-4


def test_the_cell_rehearses_untraced():
    p = _run(["--workload", CELL, "--rehearse"])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == 5 and last["rehearsal"] and not last["correct"]
    assert last["failed"] == 0 and "serve_tokens_per_s" in last["metrics"]
    assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_file_is_the_catalogs_row_but_for_its_four_cuts():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in ln)
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
    assert cfg["num_hidden_layers"] == 14 == len(
        cfg["hybrid_override_pattern"])
    assert cfg["hybrid_override_pattern"] == (
        row["config"]["hybrid_override_pattern"][:14]) == "MEMEM*EMEMEM*E"
    assert cfg["n_routed_experts"] == 64 and cfg["vocab_size"] == 65536
    assert cfg["experts_first"] == 0
    # the five aliases harness/ssm_roofline.py reads
    for alias, published in ALIASES.items():
        assert cfg[alias] == cfg[published] == row["config"][published]
    assert "aliases" in cfg["assumed"]
    assert "NemotronHAttention applies none" in cfg["assumed"][
        "no_position_embedding"]
    assert len(entry["why"]) <= 200
    assert "v5e-8" in cfg["deployment"] and "HALF" in cfg["deployment"]
    # the bytes as reckoned from the built model
    assert "4,584.9 M" in cfg["bytes"]
    # every tolerance stands beside its readings
    assert "SOUND" in cfg["probe"]["margin_reason"]


def test_the_flags_carry_the_published_widths():
    cell = spec.load_cell(CELL)
    cfg, flags = cell.config, cell.config["program"]["flags"]
    for flag in ("--model_name=nemotron_h", "--num_layers=14",
                 f"--hidden_size={cfg['hidden_size']}",
                 f"--num_attention_heads={cfg['num_attention_heads']}",
                 f"--num_attention_heads_kv={cfg['num_key_value_heads']}",
                 f"--kv_channels={cfg['head_dim']}",
                 f"--ffn_hidden_size={cfg['moe_intermediate_size']}",
                 "--mlp_activation=" + cfg["mlp_hidden_act"],
                 "--hybrid_override_pattern=" + cfg["hybrid_override_pattern"],
                 f"--num_experts={cfg['n_routed_experts']}",
                 f"--moe_router_experts={cfg['published']['n_routed_experts']}",
                 "--moe_experts_first=0",
                 f"--moe_top_k={cfg['num_experts_per_tok']}",
                 "--norm_topk_prob=1", "--moe_score_function=sigmoid",
                 "--moe_choice_bias=1", "--moe_choice_bias_std=0.02",
                 f"--moe_routed_scale={cfg['routed_scaling_factor']}",
                 "--moe_shared_experts=2",
                 f"--mamba_n_heads={cfg['mamba_num_heads']}",
                 f"--mamba_d_head={cfg['mamba_head_dim']}",
                 f"--mamba_d_state={cfg['ssm_state_size']}",
                 f"--mamba_n_groups={cfg['n_groups']}",
                 f"--mamba_d_conv={cfg['conv_kernel']}",
                 f"--mamba_chunk_size={cfg['chunk_size']}",
                 "--position_embedding_type=none",
                 "--layernorm_epsilon=1e-05", "--bf16",
                 "--vocab_size=65535", "--serve_num_slots=64",
                 "--serve_prefill_chunk=512", "--serve_block_size=16",
                 "--serve_max_model_len=6144", "--serve_preemption=0"):
        assert flag in flags, flag
    assert cfg["moe_shared_expert_intermediate_size"] == (
        2 * cfg["moe_intermediate_size"])
    assert cfg["intermediate_size"] == cfg["moe_intermediate_size"] == 1856
    assert cfg["mamba_num_heads"] * cfg["mamba_head_dim"] == 4096 != (
        cfg["expand"] * cfg["hidden_size"])
    small = cfg["program"]["rehearsal_flags"]
    for flag in ("--moe_router_experts=8", "--num_experts=4",
                 "--position_embedding_type=none", "--mamba_n_groups=2",
                 "--ffn_hidden_size=96", "--mlp_activation=relu2",
                 "--hybrid_override_pattern=MEMEM*EMEMEM*E"):
        assert flag in small, flag


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration():
    cell = spec.load_cell(CELL)
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"],
            t["trace_seconds"]) == ("closed_loop", 64, 0, 3)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 256,
                                  "max": 2048}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 512,
                                  "max": 4096}
    assert t["order_seed"] == spec.load_cell(GRANITE).traffic["order_seed"]
    assert "open_after_answers_reason" in t
    src = traffic.ClosedLoopSource(t, 1, 65536)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    assert 830 < np.mean([len(d.prompt) for d in docs]) < 890
    assert 1680 < np.mean([d.answer_tokens for d in docs]) < 1760
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert "--serve_max_model_len=6144" in flags and longest <= 6144
    assert max(max(d.prompt) for d in docs) < 65536
    # every slot's longest request fits the FULL pool at once: callers
    # are served from the slots and not from the queue
    blocks = int(next(f for f in flags if f.startswith(
        "--serve_num_blocks=")).split("=")[1])
    assert (blocks - 1) * 16 == 64 * 6144
    slots = int(next(f for f in flags if f.startswith(
        "--serve_num_slots=")).split("=")[1])
    assert t["callers"] == slots == 64
    # the probe decodes long: three chunks, 256 answer tokens
    p = cell.config["probe"]
    assert (p["prompt_tokens"], p["answer_tokens"], p["prefill_rows"]) == (
        1536, 256, 2)
    bench = spec.load_benchmark()
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ())}
    granites = {m["name"] for m in bench["per_layer"]
                if GRANITE in m.get("workloads", ())}
    assert reported == granites | {NEW}
    assert {"ssm_decode_roofline", "ssm_busy_pct", "ssm_scan_busy_pct",
            "ssm_state_copy_busy_pct", "moe_held_assignments_pct",
            "ssm_state_held_gb", "moe_routing_busy_pct",
            "moe_shared_busy_pct", "kv_pool_copy_busy_pct",
            "batch_occupancy", "serve_device_idle_pct",
            "serve_peak_hbm_gb", "device_unattributed_pct"} <= reported
    assert not reported & {"decode_roofline", "prefill_roofline",
                           "moe_roofline"}
    # every metric it is listed under moves a metric it reports
    moves = {m["moves"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert moves == {"serve_tokens_per_s"}
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert served["workloads"][-1] == CELL
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert bench["per_layer"][-1]["name"] == NEW
    assert bench["per_layer"][-1]["workloads"] == [CELL]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_new_metric_reads_the_new_counter_and_the_kernels_name():
    import inspect

    from megatron_llm_tpu.ops import paged_kv
    from megatron_llm_tpu.ops.pallas import grouped_matmul
    from megatron_llm_tpu.serving import loop_profiler

    cell = spec.load_cell(CELL)
    roof = next(m for m in cell.per_layer if m.name == NEW)
    assert roof.source == "moe_ungated_roofline_share"
    assert roof.params == {"pattern": "^moe_experts"}
    assert 'name="moe_experts"' in inspect.getsource(
        grouped_matmul.grouped_matmul)
    assert "moe_experts_touched_held" in loop_profiler.MOE_FIELDS
    assert "moe_experts_touched_held" in inspect.getsource(
        paged_kv.CachePlan.account_routing)
    body = json.load(open(os.path.join(
        spec.BENCH_DIR, "layer_metrics", NEW + ".json")))
    assert body["cells"] == [CELL] and body["unit"] == "%"
    # the state-space share reads the file's aliases: 2,134,016 B a live
    # row a layer
    from harness import ssm_roofline

    assert ssm_roofline.row_bytes(cell.config) == (
        64 * 64 * 128 * 4 + 3 * 6144 * 2) == 2134016


def test_the_scopes_reach_the_instruction_tables_of_one_sublayer_layers():
    import jax

    from megatron_llm_tpu.models.nemotron_h import (NemotronHModel,
                                                    nemotron_h_config)
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    model = NemotronHModel(nemotron_h_config("tiny", use_flash_attn=False))
    eng = InferenceEngine(
        model, model.init(jax.random.PRNGKey(0)),
        EngineConfig(num_slots=2, block_size=16, max_model_len=64,
                     prefill_chunk=16, preemption=False))
    eng.warmup()
    tables = eng.program_tables()
    want = {"engine_prefill": "ssm_scan", "engine_decode": "ssm_step"}
    for name, recurrence in want.items():
        scopes = {r["scope"] for r in tables[name].rows}
        assert {"ssm_in_proj", "ssm_conv", recurrence, "ssm_gate_norm",
                "ssm_out_proj", "moe_shared", "moe_route", "moe_dispatch",
                "moe_combine", "kv_write", "attention"} <= scopes, (
                    name, scopes)


# ---------------------------------------------------------------------------
# the new roofline on made-up records
# ---------------------------------------------------------------------------

NEMOTRON = {"hidden_size": 2688, "intermediate_size": 1856}


def _rec(**fields):
    return types.SimpleNamespace(kind="decode", **fields)


def test_an_ungated_expert_is_two_matrices_at_the_published_width():
    assert moe_ungated_roofline.expert_params(NEMOTRON) == 9977856
    flops, nbytes = moe_ungated_roofline.expert_matrices_cost(
        NEMOTRON, 192, 61)
    assert flops == 192 * 4 * 2688 * 1856
    assert nbytes == (61 * 9977856 + 192 * 2 * 2688) * 2
    secs, bound = moe_ungated_roofline.least_seconds(NEMOTRON, 192, 61, PEAKS)
    # a decode step's layer: 1.2 GB of matrices, bandwidth's
    assert bound == "bandwidth" and secs == pytest.approx(nbytes / 819e9)
    assert 1.4e-3 < secs < 1.6e-3
    # a chunk's 512 tokens x 6 choices, half of them held: compute's
    secs, bound = moe_ungated_roofline.least_seconds(NEMOTRON, 1536, 64,
                                                     PEAKS)
    assert bound == "bandwidth"     # 64 experts are 1.28 GB: 1.56 ms
    assert moe_ungated_roofline.least_seconds(
        NEMOTRON, 64 * 1536, 64, PEAKS)[1] == "compute"


def test_the_share_sums_the_records_and_never_reads_above_100():
    recs = [_rec(moe_assignments_held=192, moe_experts_touched_held=61 * 6),
            _rec(moe_assignments_held=0, moe_experts_touched_held=0),
            _rec(moe_assignments_held=1536, moe_experts_touched_held=384)]
    least = share.least_total(NEMOTRON, recs, PEAKS)
    assert least == pytest.approx(sum(
        moe_ungated_roofline.least_seconds(NEMOTRON, a, e, PEAKS)[0]
        for a, e in ((192, 366), (1536, 384))))
    # a kernel that reads every touched held expert's two matrices ONCE
    # at the chip's whole bandwidth, its rows in and out, and nothing
    # else takes exactly the least time: 100%, and any real one longer
    ideal = sum((e * 9977856 + a * 2 * 2688) * 2 / 819e9
                for a, e in ((192, 366), (1536, 384)))
    assert 100.0 * least / ideal == pytest.approx(100.0)
    assert 100.0 * least / (ideal * 1920 / 1856) < 100.0
    # a record without the field (the parent), nothing held: no reading
    assert share.least_total(NEMOTRON, [_rec(moe_assignments_held=5)],
                             PEAKS) is None
    assert share.least_total(NEMOTRON, [recs[1]], PEAKS) is None
    run = Run(cell=types.SimpleNamespace(config=dict(NEMOTRON)), seed=0,
              seconds=10.0, traced=True, rehearsal=False, process_start=0.0)
    run.peaks = PEAKS
    assert share.read(run, "^moe_experts") is None          # no trace


@pytest.mark.parametrize("control", ["no_shared", "norm_whole"])
def test_a_fault_in_the_programs_place_fails_the_probe(control):
    """``nemotron_h_controls.py --control`` plants a fault in the program
    and runs the cell through the harness (rehearsed: float32, tiny): the
    probe's comparison of the ENGINE's logits reads it beyond a limit of
    the configuration file and the run's checks say so.  (The others are
    the chip's to show: the file's ``probe.margin_reason`` has them.)"""
    root = os.path.dirname(spec.BENCH_DIR)
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "nemotron_h_controls.py"),
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
