"""The cell PR 58 added, rehearsed on the CPU with its per-layer metrics:
``docs-32k`` prefills in chunks and decodes through gated delta-rule
layers whose state is carried in a slot beside the pages of the two
attention layers; the configuration file against the catalog's row; each
control of ``qwen3_next_controls.py`` told by the probe at a small size;
and the new roofline's arithmetic against a hand count.  Entries of
``BENCHMARK.json`` are asserted BY NAME, not by position: the next append
must not turn this file red."""
import json
import os
import types

import numpy as np
import pytest

from harness import delta_roofline, spec, traffic
from test_new_cells import _rehearse, _run

CELL = "qwen3-next-80b-a3b-serve.docs-32k"
CONFIG = "qwen3-next-80b-a3b-serve"
NEW = ["delta_busy_pct", "delta_chunk_busy_pct", "delta_rows_per_launch",
       "delta_step_roofline"]
SCOPES = ["delta_proj", "delta_conv", "delta_gate", "delta_chunk",
          "delta_step", "delta_norm"]
REDUCED = ["num_hidden_layers", "num_experts"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONTROLS = ("no_delta", "no_beta", "no_decay", "decay_after", "no_l2norm",
            "no_q_scale", "kv_neighbour", "state_not_handed_on",
            "conv_not_handed_on", "no_z_gate", "norm_after_gate",
            "state_bf16", "full_rotary", "no_attn_gate", "no_shared_gate",
            "scale_is_w", "nine_experts", "float8_activations")

share = spec.load_module("sources", "delta_roofline_share")


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


def test_the_cell_rehearses_over_pages_and_a_state_group(rehearsed):
    last, lines = rehearsed
    assert not {"itl_p95_ms", "ttft_p50_ms", "moe_roofline",
                "prefill_roofline", "decode_roofline", "ssm_busy_pct",
                "kv_held_bytes_per_token", "retention_rows_per_launch"
                } & set(last["metrics"])
    assert {"ssm_state_held_gb", "delta_rows_per_launch", "batch_occupancy",
            "moe_held_assignments_pct", "prefix_hit_pct"} <= set(
                last["metrics"])
    # the probe's tight comparison is of the ENGINE's own logits over its
    # state group and pages, and of the state in the slot; float32 in a
    # rehearsal
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True and probe["answered_alike"]
    assert probe["step_token_deficit_worst"] == 0.0
    assert probe["delta_rows_live"] > 0 and probe["delta_tokens"] > 0
    assert 0 < probe["moe_assignments_held"] < probe["moe_assignments"]
    state = probe["state"]
    assert state["layers"] == 6 and state["within"] is True
    assert state["first_layer_apart"] <= state["worst"] < 1e-5
    # the drawn gates: half-lives from tens to thousands of tokens
    short, long = state["half_life_tokens"]
    assert 16 <= short < 40 and 500 < long <= 2048
    # a rehearsal runs tiny widths: they are not the file's, and say so
    assert {"head_dim", "linear_num_value_heads", "num_experts",
            "routed_experts"} <= set(probe["differs_from_the_file"])
    small = spec.load_cell(CELL).config["probe"]["rehearsal"]
    assert probe["prefill"]["positions"] == len(small["tapped_chunks"]) + 1
    assert probe["decode"]["positions"] == small["answer_tokens"] - 1
    assert probe["prefill"]["worst"] < 1e-4
    assert probe["decode"]["worst"] < 1e-4


def test_the_cell_rehearses_untraced():
    p = _run(["--workload", CELL, "--rehearse"])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == 5 and last["rehearsal"] and not last["correct"]
    assert last["failed"] == 0
    assert set(last["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_is_told_by_the_probe_at_a_small_size(control):
    """A fault planted in the program's place, the cell's own engine,
    traffic and probe at the rehearsal's sizes: the probe's comparison of
    the engine's logits and state with the reference's says no."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "qwen3_next_controls.py"),
         "--control", control, "--", "--workload", CELL, "--rehearse",
         "--seconds", "1"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert any(ln.get("planted") == control for ln in lines), p.stderr[-2000:]
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    # told: beyond the file's limits, or a thousand times the sound
    # rehearsal's distance, which is float32's 1e-6
    worst = max(probe["prefill"]["worst"], probe["decode"]["worst"],
                probe["state"]["worst"])
    assert probe["within"] is False or worst > 1e-3, (control, worst)
    if control in ("state_bf16", "no_delta", "decay_after", "no_beta",
                   "no_decay", "kv_neighbour", "state_not_handed_on"):
        # the state in the slot says so by itself
        assert probe["state"]["worst"] > 1e-4, probe["state"]


def test_the_file_is_the_catalogs_row_but_for_its_two_cuts():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"name": "Qwen3-Next-80B-A3B-Instruct"' in ln)
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert cell.config_name == CONFIG
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert set(cfg["published"]) == set(REDUCED)
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (8, 128)
    # two whole periods, a quarter of the experts, no width cut and the
    # whole vocabulary held
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
            cfg["vocab_size"]) == (2048, 5120, 256, 512, 10, 128, 128,
                                   151936)
    for item in ("norm_scale", "q_and_gate", "qk_norm", "rotary", "l2norm",
                 "key_head_of_a_value_head", "state_dtype", "gates_draw",
                 "chunk_block", "chunk_rounding", "shared_expert_gate",
                 "router", "mtp", "intermediate_size"):
        assert cfg["assumed"][item], item
    assert len(entry["why"]) <= 200
    assert "six pipeline stages of 8 layers" in cfg["deployment"]
    assert "experts 0-127" in cfg["deployment"]
    assert "QUARTER" in cfg["deployment"]
    # ISSUE 58's arithmetic, from the keys
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kh, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps, f = cfg["linear_conv_kernel_dim"], cfg["moe_intermediate_size"]
    full = h * nh * 2 * d + 2 * h * nkv * d + nh * d * h + 2 * d
    cdim = 2 * kh * dk + hv * dv
    delta = (h * (cdim + hv * dv) + h * 2 * hv + cdim * taps + 2 * hv + dv
             + hv * dv * h)
    every = (2 * h + h * cfg["published"]["num_experts"] + 3 * h * f + h
             + cfg["num_experts"] * 3 * h * f)
    b = cfg["bytes"]
    assert b["parameters"] == (2 * v * h + h + 8 * every + 2 * full
                               + 6 * delta) == 4_133_998_720
    assert b["weights_gb"] == pytest.approx(2 * b["parameters"] / 1e9,
                                            abs=1e-3)
    assert b["kv_bytes_a_token"] == 2 * nkv * d * 2 * 2 == 4096
    assert b["delta_state_bytes_a_row_a_layer"] == hv * dk * dv * 4
    assert b["conv_state_bytes_a_row_a_layer"] == (taps - 1) * cdim * 2
    assert b["state_bytes_a_slot"] == 6 * (2_097_152 + 49_152)
    assert b["state_gb"] == pytest.approx(33 * b["state_bytes_a_slot"] / 1e9,
                                          abs=1e-3)
    assert b["pool_gb"] == pytest.approx(32769 * 16 * 4096 / 1e9, abs=1e-3)
    # weights, the pool and the states held twice by a chunk, on the chip
    assert (b["weights_gb"] + 2 * (b["pool_gb"] + b["state_gb"])) < 15.75


def test_the_flags_carry_the_published_widths():
    cell = spec.load_cell(CELL)
    cfg, flags = cell.config, cell.config["program"]["flags"]
    for flag in ("--model_name=qwen3_next", "--num_layers=8",
                 f"--hidden_size={cfg['hidden_size']}",
                 f"--num_attention_heads={cfg['num_attention_heads']}",
                 f"--num_attention_heads_kv={cfg['num_key_value_heads']}",
                 f"--kv_channels={cfg['head_dim']}",
                 f"--ffn_hidden_size={cfg['intermediate_size']}",
                 f"--moe_ffn_hidden_size={cfg['moe_intermediate_size']}",
                 "--num_experts=128", "--moe_router_experts=512",
                 "--moe_experts_first=0", "--moe_top_k=10",
                 "--norm_topk_prob=1", "--moe_shared_experts=1",
                 "--moe_shared_expert_gate", "--qk_norm_per_head",
                 "--attention_output_gate", "--rotary_percent=0.25",
                 f"--rope_theta={cfg['rope_theta']}",
                 "--layernorm_epsilon=1e-06",
                 f"--delta_key_heads={cfg['linear_num_key_heads']}",
                 f"--delta_value_heads={cfg['linear_num_value_heads']}",
                 f"--delta_key_dim={cfg['linear_key_head_dim']}",
                 f"--delta_value_dim={cfg['linear_value_head_dim']}",
                 f"--delta_conv_taps={cfg['linear_conv_kernel_dim']}",
                 f"--max_position_embeddings={cfg['max_position_embeddings']}",
                 "--bf16", "--vocab_size=151935", "--serve_num_slots=32",
                 "--serve_num_blocks=32769", "--serve_prefill_chunk=512",
                 "--serve_max_model_len=33792", "--serve_preemption=0"):
        assert flag in flags, flag
    at = flags.index("--layer_types")
    assert flags[at + 1:at + 5] == ["gated_delta"] * 3 + ["attention"]
    assert flags[at + 5].startswith("--")
    small = cfg["program"]["rehearsal_flags"]
    # the same pattern, a share of the experts and a partial rotary
    for flag in ("--model_name=qwen3_next", "--num_layers=8",
                 "--num_experts=8", "--moe_router_experts=16",
                 "--rotary_percent=0.25", "--moe_shared_expert_gate",
                 "--attention_output_gate", "--serve_preemption=0"):
        assert flag in small, flag
    at = small.index("--layer_types")
    assert small[at + 1:at + 5] == ["gated_delta"] * 3 + ["attention"]


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration():
    cell = spec.load_cell(CELL)
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert cell.traffic_name == "docs-32k" and cell.chips == 1
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"],
            t["trace_seconds"], t["open_after_answers"]) == (
                "closed_loop", 32, 0, 3, 16)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 4096,
                                  "max": 32768}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 256,
                                  "max": 1024}
    assert (t["strata_requests"], t["documents_per_cycle"]) == (8, 96)
    assert t["order_seed"] not in (23, 51, 54)   # an order_seed of its own
    assert "rehearsal" in t
    src = traffic.ClosedLoopSource(t, 1, 151936)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    assert 13400 < np.mean([len(d.prompt) for d in docs]) < 14200
    assert 540 < np.mean([d.answer_tokens for d in docs]) < 570
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert longest <= 32768 + 1024 <= 33792
    assert max(max(d.prompt) for d in docs) < 151936

    def flag(name):
        return int(next(f for f in flags if f.startswith(
            f"--{name}=")).split("=")[1])

    assert t["callers"] == flag("serve_num_slots") == 32
    # every caller's longest request has its pages
    assert (flag("serve_num_blocks") - 1) * flag("serve_block_size") >= (
        32 * 14400)
    # the probe prefills and decodes as its cell does: nine chunks
    p = cell.config["probe"]
    assert (p["prompt_tokens"], p["answer_tokens"], p["tapped_chunks"]) == (
        4608, 24, [2, 5, 9])
    assert p["prompt_tokens"] == 9 * flag("serve_prefill_chunk")
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    reported = {n for n, m in by_name.items()
                if CELL in m.get("workloads", ())}
    assert set(NEW) <= reported
    assert {"batch_occupancy", "prefix_hit_pct", "ttft_p50_ms.docqa",
            "prefill_chunk_wall_ms", "prefill_program_ms",
            "serve_device_idle_pct", "serve_peak_hbm_gb",
            "loop_build_inputs_ms", "loop_emit_ms", "idle_explained_pct",
            "prefill_launch_device_ms", "device_unattributed_pct",
            "ssm_state_held_gb", "ssm_state_copy_busy_pct",
            "setup_trace_lower_s", "launch_stall_pct",
            "attention_full_busy_pct", "kv_pool_copy_busy_pct",
            "attn_gate_busy_pct", "moe_routing_busy_pct",
            "moe_shared_busy_pct", "moe_combine_busy_pct",
            "moe_held_assignments_pct", "moe_gated_held_roofline"
            } <= reported
    assert len(reported) == 29 + 8 + 4
    assert not reported & {"decode_roofline", "prefill_roofline",
                           "kv_held_bytes_per_token", "ssm_busy_pct",
                           "ssm_decode_roofline", "retention_busy_pct",
                           "decode_program_ms", "decode_step_wall_ms"}
    assert {by_name[n]["moves"] for n in reported} == {"serve_tokens_per_s",
                                                       "setup_s"}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    assert by_name["delta_step_roofline"]["unit"] == "%"
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in served["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "docs-32k", 1)
    assert len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert sum(w["config"] == CONFIG for w in bench["workloads"]) == 1


def test_the_new_metrics_read_the_new_scopes_counters_and_kernel():
    import inspect

    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.models import gated_delta
    from megatron_llm_tpu.ops.pallas import delta_step
    from megatron_llm_tpu.serving import loop_profiler

    cell = spec.load_cell(CELL)
    by_name = {m.name: m for m in cell.per_layer}
    assert by_name["delta_busy_pct"].params == {
        "what": "busy_share", "scope": SCOPES}
    assert by_name["delta_chunk_busy_pct"].params == {
        "what": "busy_share", "scope": ["delta_chunk"]}
    assert by_name["delta_rows_per_launch"].source == "loop_record_mean"
    assert by_name["delta_rows_per_launch"].params == {
        "field": "delta_rows_live"}
    roof = by_name["delta_step_roofline"]
    assert roof.source == "delta_roofline_share"
    assert roof.params == {"scopes": ["delta_step"]}
    source = inspect.getsource(gated_delta.gated_delta_mixer)
    for scope in SCOPES:
        assert scope in hlo_collectives.SCOPES
        assert f'named_scope("{scope}"' in source
    assert set(loop_profiler.DELTA_FIELDS) >= {
        "delta_rows_live", "delta_rows_moved", "delta_tokens"}
    assert set(loop_profiler.DELTA_FIELDS) <= set(
        loop_profiler.COUNTED_FIELDS)
    assert 'name="delta_state_step"' in inspect.getsource(delta_step)
    # the held experts' roofline takes its two widths from the file
    assert (cell.config["hidden_size"],
            cell.config["moe_intermediate_size"]) == (2048, 512)


# ---------------------------------------------------------------------------
# the new roofline against a hand count
# ---------------------------------------------------------------------------

def _rec(kind, **fields):
    return types.SimpleNamespace(kind=kind, **fields)


def test_a_row_of_a_layer_is_2_mebibytes_read_and_written_once():
    cfg = dict(spec.load_cell(CELL).config)
    # 32 value heads of S [128, 128] in float32
    by_hand = 32 * 128 * 128 * 4
    assert delta_roofline.row_bytes(cfg) == by_hand == 2_097_152
    assert by_hand == cfg["bytes"]["delta_state_bytes_a_row_a_layer"]
    # a step at 30 live rows over 6 layers: 0.755 GB, 0.92 ms
    secs = delta_roofline.decode_least_seconds(cfg, 30 * 6, PEAKS)
    assert secs == pytest.approx(2 * 180 * by_hand / 819e9)
    assert 0.9e-3 < secs < 0.95e-3


def test_the_share_sums_the_decode_launches_inside_the_window():
    cfg = dict(spec.load_cell(CELL).config)
    rows = [(_rec("decode", delta_rows_live=192), 1.0, 1.03),
            (_rec("prefill", delta_rows_live=6), 1.03, 1.06),
            (_rec("decode", delta_rows_live=180), 1.06, 1.09),
            (_rec("decode", delta_rows_live=192), 1.09, 2.5)]
    ops = [(0, 1.0, 1.002, {"scope": "delta_step"}, 0, None),
           (0, 1.015, 1.02, {"scope": "mlp"}, 0, None),
           (0, 1.04, 1.05, {"scope": "delta_chunk"}, 1, None),
           (0, 1.06, 1.062, {"scope": "delta_step"}, 2, None),
           (0, 1.1, 1.2, {"scope": "delta_step"}, 3, None)]
    least, measured = share.least_and_measured(
        cfg, rows, ops, (0.9, 2.0), ("delta_step",), PEAKS)
    assert least == pytest.approx(2 * 372 * 2_097_152 / 819e9)
    assert measured == pytest.approx(0.004)
    assert 0 < 100 * least / measured < 100
    # records of a program that lacks the field (the parent): nothing
    old = [(_rec("decode"), 1.0, 1.03)]
    assert share.least_and_measured(cfg, old, ops, (0.9, 2.0),
                                    ("delta_step",), PEAKS) is None
    # no trace: nothing, and no error
    run = types.SimpleNamespace(setup_parts={}, trace=None, peaks=PEAKS)
    assert share.read(run, ["delta_step"]) is None
