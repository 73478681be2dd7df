"""The cell PR 54 added, rehearsed on the CPU with its per-layer metrics:
``continue-16k`` prefills in chunks and decodes through retention layers
whose state is carried in a slot of a pool that has NO page; the
configuration file against the catalog's row; each control of
``brumby_controls.py`` told by the probe at a small size; and the new
roofline's arithmetic against a hand count.  Entries of
``BENCHMARK.json`` are asserted BY NAME, not by position: the next append
must not turn this file red."""
import json
import os
import types

import numpy as np
import pytest

from harness import retention_roofline, spec, traffic
from test_new_cells import _rehearse, _run

CELL = "brumby-14b-serve.continue-16k"
CONFIG = "brumby-14b-serve"
NEW = ["retention_busy_pct", "retention_chunk_busy_pct",
       "retention_rows_per_launch", "retention_step_roofline"]
REDUCED = ["num_hidden_layers"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONTROLS = ("state_bf16", "step_products_bf16", "state_not_handed_on",
            "no_sqrt2", "kv_neighbour", "no_gate", "sum_not_decayed",
            "own_term_decayed", "no_normaliser", "degree_one", "no_rope",
            "no_qk_norm", "float8_activations")

share = spec.load_module("sources", "retention_roofline_share")


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


def test_the_cell_rehearses_over_a_pool_with_no_page(rehearsed):
    last, lines = rehearsed
    assert not {"itl_p95_ms", "ttft_p50_ms", "moe_roofline",
                "prefill_roofline", "decode_roofline", "ssm_busy_pct",
                "kv_held_bytes_per_token", "kv_pool_copy_busy_pct",
                "moe_routing_busy_pct"} & set(last["metrics"])
    assert {"ssm_state_held_gb", "retention_rows_per_launch",
            "batch_occupancy", "prefix_hit_pct"} <= set(last["metrics"])
    # the probe's tight comparison is of the ENGINE's own logits over its
    # state group, and of the state in the slot; float32 in a rehearsal
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True and probe["answered_alike"]
    assert probe["paged"] is False
    assert probe["step_token_deficit_worst"] == 0.0
    assert probe["paged_kernel"] == probe["prefill_kernel"] == "pallas"
    assert probe["retention_rows_live"] > 0
    assert probe["retention_tokens"] > 0
    # 8 layers of 2 key-value heads of 9 rotations of [16, 16] and [16]
    assert probe["state_bytes_a_slot"] == 8 * 2 * 9 * (16 * 16 + 16) * 4
    assert probe["state"]["state_apart"] < 1e-5
    assert probe["state"]["sum_apart"] < 1e-5
    # a rehearsal runs tiny widths: they are not the file's, and say so
    assert set(probe["differs_from_the_file"]) == {"head_dim", "phi_rows"}
    small = spec.load_cell(CELL).config["probe"]["rehearsal"]
    assert len(probe["prefill"]["positions"]) == len(
        small["tapped_chunks"]) + 1
    assert probe["decode"]["positions"] == small["answer_tokens"] - 1
    assert max(probe["prefill"]["apart"]) < 1e-4
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
                                      "brumby_controls.py"),
         "--control", control, "--", "--workload", CELL, "--rehearse",
         "--seconds", "1"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert any(ln.get("planted") == control for ln in lines), p.stderr[-2000:]
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    # told: beyond the file's limits, or a thousand times the sound
    # rehearsal's distance, which is float32's 1e-6
    worst = max(max(probe["prefill"]["apart"]), probe["decode"]["worst"],
                probe["state"]["state_apart"], probe["state"]["sum_apart"])
    assert probe["within"] is False or worst > 1e-3, (control, worst)
    if control in ("state_bf16", "step_products_bf16"):
        # the state in the slot says so by itself
        assert probe["state"]["state_apart"] > 1e-4, probe["state"]


def test_the_file_is_the_catalogs_row_but_for_its_one_cut():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"name": "Brumby-14B-Base"' in ln)
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
    assert cfg["num_hidden_layers"] == 8
    # no width is cut, and the whole vocabulary is held
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["vocab_size"]) == (5120, 17408, 128, 40, 8, 151936)
    for item in ("mixer", "retention_degree", "gate", "normaliser", "qk_norm",
                 "rotary", "state_dtype", "phi_layout", "phi_rounding",
                 "chunk_block"):
        assert cfg["assumed"][item], item
    assert len(entry["why"]) <= 200
    assert "five pipeline stages of 8 layers" in cfg["deployment"]
    # ISSUE 54's arithmetic, and the state at the program's 8,320 rows
    b = cfg["bytes"]
    assert b["parameters"] == 8 * 330_352_896 + 2 * 151936 * 5120 + 5120
    assert b["kv_bytes_a_token"] == 0 and b["phi_rows"] == 65 * 128
    assert b["state_bytes_a_slot"] == 8 * 8 * (8320 * 128 + 8320) * 4
    assert b["weights_gb"] + b["state_gb"] < 15.75 - 2.4


def test_the_flags_carry_the_published_widths():
    cell = spec.load_cell(CELL)
    cfg, flags = cell.config, cell.config["program"]["flags"]
    for flag in ("--model_name=brumby", "--num_layers=8",
                 f"--hidden_size={cfg['hidden_size']}",
                 f"--num_attention_heads={cfg['num_attention_heads']}",
                 f"--num_attention_heads_kv={cfg['num_key_value_heads']}",
                 f"--kv_channels={cfg['head_dim']}",
                 f"--ffn_hidden_size={cfg['intermediate_size']}",
                 "--qk_norm_per_head", f"--rope_theta={cfg['rope_theta']}",
                 "--layernorm_epsilon=1e-06",
                 f"--max_position_embeddings={cfg['max_position_embeddings']}",
                 "--bf16", "--vocab_size=151935", "--serve_num_slots=16",
                 "--serve_prefill_chunk=512", "--serve_max_model_len=17920",
                 "--serve_preemption=0"):
        assert flag in flags, flag
    at = flags.index("--layer_types")
    assert flags[at + 1] == "retention" and flags[at + 2].startswith("--")
    # a pool with no page is sized by nothing
    assert not any(f.startswith("--serve_num_blocks") for f in flags)
    small = cfg["program"]["rehearsal_flags"]
    for flag in ("--model_name=brumby", "--num_layers=8", "--kv_channels=16",
                 "--num_attention_heads=4", "--num_attention_heads_kv=2",
                 "--serve_preemption=0"):
        assert flag in small, flag


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration():
    cell = spec.load_cell(CELL)
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert cell.traffic_name == "continue-16k" and cell.chips == 1
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"],
            t["trace_seconds"], t["open_after_answers"]) == (
                "closed_loop", 16, 0, 3, 8)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 2048,
                                  "max": 16384}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 256,
                                  "max": 1024}
    assert (t["strata_requests"], t["documents_per_cycle"]) == (8, 96)
    assert t["order_seed"] not in (23, 51)       # an order_seed of its own
    src = traffic.ClosedLoopSource(t, 1, 151936)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    assert 6700 < np.mean([len(d.prompt) for d in docs]) < 7100
    assert 540 < np.mean([d.answer_tokens for d in docs]) < 570
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert longest <= 16384 + 1024 <= 17920
    assert max(max(d.prompt) for d in docs) < 151936

    def flag(name):
        return int(next(f for f in flags if f.startswith(
            f"--{name}=")).split("=")[1])

    assert t["callers"] == flag("serve_num_slots") == 16
    # the probe prefills and decodes as its cell does: nine chunks, so
    # that the context passes the 4,128 tokens a state is worth
    p = cell.config["probe"]
    assert (p["prompt_tokens"], p["answer_tokens"], p["tapped_chunks"]) == (
        4608, 256, [2, 5, 9])
    assert p["prompt_tokens"] > 8320 * 128 // (2 * 128)
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
            "prefill_launch_device_ms", "device_unattributed_pct",
            "ssm_state_held_gb", "ssm_state_copy_busy_pct",
            "setup_trace_lower_s", "launch_stall_pct"} <= reported
    assert not reported & {"decode_roofline", "prefill_roofline",
                           "kv_pool_copy_busy_pct",
                           "kv_held_bytes_per_token", "ssm_busy_pct",
                           "ssm_decode_roofline"}
    assert not any(n.startswith("moe_") for n in reported)
    assert {by_name[n]["moves"] for n in reported} == {"serve_tokens_per_s",
                                                       "setup_s"}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    assert by_name["retention_step_roofline"]["unit"] == "%"
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in served["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "continue-16k", 1)
    assert len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert sum(w["config"] == CONFIG for w in bench["workloads"]) == 1


def test_the_new_metrics_read_the_new_scopes_counters_and_kernel():
    import inspect

    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.models import retention
    from megatron_llm_tpu.ops.pallas import retention_step
    from megatron_llm_tpu.serving import loop_profiler

    cell = spec.load_cell(CELL)
    by_name = {m.name: m for m in cell.per_layer}
    assert by_name["retention_busy_pct"].params == {
        "what": "busy_share",
        "scope": ["retention_gate", "retention_chunk", "retention_step"]}
    assert by_name["retention_chunk_busy_pct"].params == {
        "what": "busy_share", "scope": ["retention_chunk"]}
    assert by_name["retention_rows_per_launch"].source == "loop_record_mean"
    assert by_name["retention_rows_per_launch"].params == {
        "field": "retention_rows_live"}
    roof = by_name["retention_step_roofline"]
    assert roof.source == "retention_roofline_share"
    assert roof.params == {"scopes": ["retention_step"]}
    source = inspect.getsource(retention.retention_mixer)
    for scope in ("retention_gate", "retention_chunk", "retention_step"):
        assert scope in hlo_collectives.SCOPES
        assert f'named_scope("{scope}")' in source
    assert loop_profiler.RETENTION_FIELDS == (
        "retention_rows_live", "retention_rows_moved", "retention_tokens")
    assert set(loop_profiler.RETENTION_FIELDS) <= set(
        loop_profiler.COUNTED_FIELDS)
    assert 'name="retention_state_step"' in inspect.getsource(retention_step)
    for name in NEW:
        body = json.load(open(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".json")))
        assert body["cells"] == [CELL]


# ---------------------------------------------------------------------------
# the new roofline against a hand count
# ---------------------------------------------------------------------------

def _rec(kind, **fields):
    return types.SimpleNamespace(kind=kind, **fields)


def test_a_row_of_a_layer_is_34_megabytes_read_and_written_once():
    cfg = dict(spec.load_cell(CELL).config)
    # 8 key-value heads of S [8320, 128] and z [8320] in float32
    by_hand = 8 * (8320 * 128 + 8320) * 4
    assert retention_roofline.row_bytes(cfg) == by_hand == 34_344_960
    assert by_hand * 8 == cfg["bytes"]["state_bytes_a_slot"]
    # a step at 16 live rows over 8 layers: 8.79 GB, 10.7 ms
    secs = retention_roofline.decode_least_seconds(cfg, 16 * 8, PEAKS)
    assert secs == pytest.approx(2 * 128 * by_hand / 819e9)
    assert 10.5e-3 < secs < 11e-3


def test_the_share_sums_the_decode_launches_inside_the_window():
    cfg = dict(spec.load_cell(CELL).config)
    rows = [(_rec("decode", retention_rows_live=128), 1.0, 1.03),
            (_rec("prefill", retention_rows_live=8), 1.03, 1.06),
            (_rec("decode", retention_rows_live=120), 1.06, 1.09),
            (_rec("decode", retention_rows_live=128), 1.09, 2.5)]
    ops = [(0, 1.0, 1.015, {"scope": "retention_step"}, 0, None),
           (0, 1.015, 1.02, {"scope": "mlp"}, 0, None),
           (0, 1.04, 1.05, {"scope": "retention_chunk"}, 1, None),
           (0, 1.06, 1.075, {"scope": "retention_step"}, 2, None),
           (0, 1.1, 1.2, {"scope": "retention_step"}, 3, None)]
    least, measured = share.least_and_measured(
        cfg, rows, ops, (0.9, 2.0), ("retention_step",), PEAKS)
    assert least == pytest.approx(2 * 248 * 34_344_960 / 819e9)
    assert measured == pytest.approx(0.03)
    assert 0 < 100 * least / measured < 100
    # records of a program that lacks the field (the parent): nothing
    old = [(_rec("decode"), 1.0, 1.03)]
    assert share.least_and_measured(cfg, old, ops, (0.9, 2.0),
                                    ("retention_step",), PEAKS) is None
    # no trace: nothing, and no error
    run = types.SimpleNamespace(setup_parts={}, trace=None, peaks=PEAKS)
    assert share.read(run, ["retention_step"]) is None
