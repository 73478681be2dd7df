"""The cell PR 51 added, rehearsed on the CPU with its per-layer metrics:
``sessions-128`` prefills in chunks and decodes as long as it prefills,
through gated short convolutions whose columns are carried in a slot and
attention of 64-wide heads held two a row of the pool; the configuration
file against the catalog's row; each control of ``lfm2_controls.py`` told
by the probe at a small size; and the new roofline's arithmetic on
made-up records.  Entries of ``BENCHMARK.json`` are asserted BY NAME, not
by position: the next append must not turn this file red."""
import json
import os
import types

import numpy as np
import pytest

from harness import paged_walk_roofline, spec, traffic
from test_new_cells import _rehearse, _run

CELL = "lfm2-8b-a1b-serve.sessions-128"
CONFIG = "lfm2-8b-a1b-serve"
NEW = ["conv_mixer_busy_pct", "short_conv_busy_pct", "conv_rows_per_launch",
       "paged_walk_d64_roofline"]
REDUCED = ["num_hidden_layers", "layer_types"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONTROLS = ("taps_reversed", "state_not_handed_on", "bc_swapped",
            "conv_activation", "bias_in_gates", "no_qk_norm", "no_rope",
            "kv_neighbour", "state_float8", "float8_activations")

share = spec.load_module("sources", "paged_walk_roofline_share")


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


def test_the_cell_rehearses_over_its_pool_and_state_group(rehearsed):
    last, lines = rehearsed
    assert not {"itl_p95_ms", "ttft_p50_ms", "moe_roofline",
                "prefill_roofline", "decode_roofline", "mla_busy_pct",
                "ssm_busy_pct", "dsa_busy_pct", "moe_shared_busy_pct",
                "kv_held_bytes_per_token"} & set(last["metrics"])
    assert {"moe_held_assignments_pct", "ssm_state_held_gb",
            "conv_rows_per_launch", "batch_occupancy"} <= set(
                last["metrics"])
    # the probe's tight comparison is of the ENGINE's own logits over its
    # pool and its state group, and of the columns in the slot; float32
    # in a rehearsal
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True
    assert probe["answered_alike"] and probe["tapped_every_row"]
    assert probe["step_token_deficit_worst"] == 0.0
    assert probe["paged_kernel"] == probe["prefill_kernel"] == "pallas"
    assert probe["conv_rows_live"] > 0 and probe["conv_tokens"] > 0
    # 2 key-value heads of 64 a token a layer at the rehearsal's widths,
    # two a row: 2 x 2 x 64 x 4 B in float32
    assert probe["kv_bytes_a_token_a_layer"] == 1024
    assert probe["state_bytes_a_slot"] == 11 * 2 * 128 * 4
    state = probe["state"]
    assert state["within"] and state["layers"] == 11
    assert state["worst"] < 1e-5
    # a rehearsal runs tiny widths: they are not the file's, and say so;
    # what is no width is the file's even there
    differs = set(probe["differs_from_the_file"])
    assert differs == {"moe_intermediate_size", "num_experts"}
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


@pytest.mark.parametrize("control", CONTROLS)
def test_each_control_is_told_by_the_probe_at_a_small_size(control):
    """A fault planted in the program's place, the cell's own engine,
    traffic and probe at the rehearsal's sizes: the probe's comparison of
    the engine's logits and columns with the reference's says no."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "lfm2_controls.py"),
         "--control", control, "--", "--workload", CELL, "--rehearse",
         "--seconds", "1"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert any(ln.get("planted") == control for ln in lines), p.stderr[-2000:]
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    # told: beyond the file's limits, or (a choice bias drawn at 0.02
    # moves a tiny model's gates by hundredths) a thousand times the
    # sound rehearsal's distance, which is float32's 3e-7
    worst = max(probe["prefill"]["worst"], probe["decode"]["worst"])
    assert probe["within"] is False or worst > 3e-4, (control, worst)
    if control == "kv_neighbour":
        # the walks ran as kernels: the fault is in their wrapper
        assert probe["paged_kernel"] == "pallas"
    if control in ("state_float8", "state_not_handed_on"):
        # the columns in the slot say so by themselves
        assert probe["state"]["within"] is False or not (
            probe["prefill"]["worst"] < 1e-4)


def test_the_file_is_the_catalogs_row_but_for_its_two_cuts():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"name": "LFM2-8B-A1B"' in ln)
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
    # the first 14 published layers as they stand: both dense layers, then
    # three whole periods full_attention conv conv conv
    assert cfg["num_hidden_layers"] == 14 == len(cfg["layer_types"])
    assert cfg["layer_types"] == row["config"]["layer_types"][:14] == (
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3)
    assert (cfg["num_dense_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (2, 32, 65536)
    # no width is cut
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (
                2048, 7168, 1792, 4, 32, 8)
    for item in ("tie_word_embeddings", "head_dim", "norms", "in_proj_order",
                 "conv", "conv_state_dtype", "qk_norm", "rope", "router",
                 "expert_bias", "kv_pool_layout"):
        assert cfg["assumed"][item], item
    assert "0.02" in cfg["assumed"]["expert_bias"]
    assert len(entry["why"]) <= 200
    assert "two pipeline stages of 14 and 10 layers" in cfg["deployment"]
    assert "4,667,077,376" in cfg["assumed"]["num_hidden_layers"]
    # every tolerance stands beside its readings
    assert "SOUND" in cfg["probe"]["margin_reason"]


def test_the_flags_carry_the_published_widths():
    cell = spec.load_cell(CELL)
    cfg, flags = cell.config, cell.config["program"]["flags"]
    for flag in ("--model_name=lfm2", "--num_layers=14",
                 f"--hidden_size={cfg['hidden_size']}",
                 f"--num_attention_heads={cfg['num_attention_heads']}",
                 f"--num_attention_heads_kv={cfg['num_key_value_heads']}",
                 "--kv_channels=64",
                 f"--ffn_hidden_size={cfg['intermediate_size']}",
                 f"--moe_ffn_hidden_size={cfg['moe_intermediate_size']}",
                 f"--num_experts={cfg['num_experts']}",
                 f"--moe_top_k={cfg['num_experts_per_tok']}",
                 f"--moe_first_dense_layers={cfg['num_dense_layers']}",
                 f"--conv_taps={cfg['conv_L_cache']}", "--conv_mixer_bias=0",
                 "--moe_score_function=sigmoid", "--moe_choice_bias=1",
                 "--moe_choice_bias_std=0.02", "--norm_topk_prob=1",
                 "--moe_gate_norm_eps=1e-06", "--moe_gate_norm_added=1",
                 "--moe_routed_scale=1.0", "--qk_norm_per_head",
                 f"--rope_theta={cfg['rope_theta']}",
                 "--layernorm_epsilon=1e-05",
                 f"--max_position_embeddings={cfg['max_position_embeddings']}",
                 "--bf16", "--vocab_size=65535", "--serve_num_slots=128",
                 "--serve_prefill_chunk=512", "--serve_block_size=16",
                 "--serve_max_model_len=4352", "--serve_preemption=0"):
        assert flag in flags, flag
    at = flags.index("--layer_types")
    names = {"conv": "conv", "attention": "full_attention"}
    assert [names[t] for t in flags[at + 1:at + 15]] == cfg["layer_types"]
    assert flags[at + 15].startswith("--")
    small = cfg["program"]["rehearsal_flags"]
    for flag in ("--kv_channels=64", "--num_layers=14",
                 "--moe_first_dense_layers=2", "--moe_gate_norm_added=1",
                 "--serve_preemption=0"):
        assert flag in small, flag
    assert small[small.index("--layer_types") + 1:][:14] == flags[at + 1:
                                                                  at + 15]


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration():
    cell = spec.load_cell(CELL)
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert cell.traffic_name == "sessions-128" and cell.chips == 1
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"],
            t["trace_seconds"], t["open_after_answers"]) == (
                "closed_loop", 128, 0, 3, 64)
    assert t["prompt_tokens"] == t["answer_tokens"] == {
        "dist": "loguniform", "min": 256, "max": 2048}
    assert (t["strata_requests"], t["documents_per_cycle"]) == (16, 384)
    assert t["order_seed"] not in (23,)          # an order_seed of its own
    src = traffic.ClosedLoopSource(t, 1, 65536)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    assert 830 < np.mean([len(d.prompt) for d in docs]) < 895
    assert 830 < np.mean([d.answer_tokens for d in docs]) < 895
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert longest <= 2048 + 2048 <= 4352
    assert max(max(d.prompt) for d in docs) < 65536

    def flag(name):
        return int(next(f for f in flags if f.startswith(
            f"--{name}=")).split("=")[1])

    assert t["callers"] == flag("serve_num_slots") == 128
    # the pool holds 128 requests' mean reservation with room to spare
    assert (flag("serve_num_blocks") - 1) * 16 == 262144
    mean = np.mean([len(d.prompt) + d.answer_tokens for d in docs])
    assert 128 * mean < 262144
    # the probe decodes as its cell does: three chunks, 256 answer tokens
    p = cell.config["probe"]
    assert (p["prompt_tokens"], p["answer_tokens"], p["prefill_rows"]) == (
        1536, 256, 2)
    assert p["prompt_tokens"] + p["answer_tokens"] <= flag(
        "serve_max_model_len")
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    reported = {n for n, m in by_name.items()
                if CELL in m.get("workloads", ())}
    assert set(NEW) <= reported
    assert {"batch_occupancy", "prefix_hit_pct", "ttft_p50_ms.docqa",
            "prefill_chunk_wall_ms", "prefill_program_ms",
            "serve_device_idle_pct", "serve_peak_hbm_gb",
            "loop_build_inputs_ms", "loop_emit_ms", "idle_explained_pct",
            "kv_pool_copy_busy_pct", "moe_routing_busy_pct",
            "moe_combine_busy_pct", "prefill_launch_device_ms",
            "device_unattributed_pct", "ssm_state_held_gb",
            "moe_held_assignments_pct", "moe_gated_held_roofline",
            "setup_trace_lower_s", "launch_stall_pct"} <= reported
    assert not reported & {"decode_roofline", "prefill_roofline",
                           "moe_roofline", "moe_ungated_roofline",
                           "moe_shared_busy_pct", "kv_held_bytes_per_token",
                           "ssm_busy_pct", "ssm_decode_roofline"}
    assert {by_name[n]["moves"] for n in reported} == {"serve_tokens_per_s",
                                                       "setup_s"}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    assert by_name["paged_walk_d64_roofline"]["unit"] == "%"
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in served["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "sessions-128", 1)
    assert len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert sum(w["config"] == CONFIG for w in bench["workloads"]) == 1


def test_the_new_metrics_read_the_new_scopes_counters_and_kernels():
    import inspect

    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.models import short_conv
    from megatron_llm_tpu.ops.pallas import paged_attention
    from megatron_llm_tpu.serving import loop_profiler

    cell = spec.load_cell(CELL)
    by_name = {m.name: m for m in cell.per_layer}
    assert by_name["conv_mixer_busy_pct"].params == {
        "what": "busy_share",
        "scope": ["conv_in_proj", "short_conv", "conv_out_proj"]}
    assert by_name["short_conv_busy_pct"].params == {
        "what": "busy_share", "scope": ["short_conv"]}
    assert by_name["conv_rows_per_launch"].source == "loop_record_mean"
    assert by_name["conv_rows_per_launch"].params == {
        "field": "conv_rows_live"}
    roof = by_name["paged_walk_d64_roofline"]
    assert roof.source == "paged_walk_roofline_share"
    assert roof.params == {"pattern": "^paged_attention_(decode|prefill)"}
    source = inspect.getsource(short_conv.short_conv_mixer)
    for scope in ("conv_in_proj", "short_conv", "conv_out_proj"):
        assert scope in hlo_collectives.SCOPES
        assert f'named_scope("{scope}")' in source
    assert loop_profiler.CONV_FIELDS == ("conv_rows_live", "conv_tokens")
    assert set(loop_profiler.CONV_FIELDS) <= set(
        loop_profiler.COUNTED_FIELDS)
    for name in ("paged_attention_decode", "paged_attention_prefill"):
        assert f'name="{name}"' in inspect.getsource(paged_attention)
    for name in NEW:
        body = json.load(open(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".json")))
        assert body["cells"] == [CELL]


# ---------------------------------------------------------------------------
# the new roofline on made-up records
# ---------------------------------------------------------------------------

def _cfg():
    return dict(spec.load_cell(CELL).config)


def _rec(kind, **fields):
    return types.SimpleNamespace(kind=kind, **fields)


def test_a_token_is_2048_bytes_in_each_of_three_attention_layers():
    cfg = _cfg()
    assert paged_walk_roofline.token_bytes(cfg) == 2 * 8 * 64 * 2 == 2048
    assert paged_walk_roofline.attention_layers(cfg) == 3
    step = _rec("decode", rows=128, context_tokens=128 * 1300)
    chunk = _rec("prefill", start=512, valid=400)
    assert paged_walk_roofline.launch_tokens(step) == 128 * 1301
    assert paged_walk_roofline.launch_tokens(chunk) == 912
    secs = paged_walk_roofline.least_seconds(cfg, 128 * 1301, PEAKS)
    # a step's three walks over a mean context of 1.3k: 1.02 GB, 1.25 ms
    assert secs == pytest.approx(128 * 1301 * 2048 * 3 / 819e9)
    assert 1.2e-3 < secs < 1.3e-3


def test_the_share_sums_the_records_and_reads_nothing_without_them():
    cfg = _cfg()
    recs = [_rec("decode", rows=128, context_tokens=128 * 1300),
            _rec("prefill", start=512, valid=400)]
    least = share.least_total(cfg, recs, PEAKS)
    assert least == pytest.approx((128 * 1301 + 912) * 6144 / 819e9)
    assert share.least_total(cfg, [], PEAKS) is None
    # no trace: nothing, and no error
    run = types.SimpleNamespace(setup_parts={}, trace=None, peaks=PEAKS)
    assert share.read(run, "^paged_attention_(decode|prefill)") is None
