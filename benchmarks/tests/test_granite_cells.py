"""The cell PR 40 added, rehearsed on the CPU with its per-layer metrics:
``sessions-16k`` prefills in chunks whose state is carried in a slot and
decodes through it, a share of the router's experts held, and prints the
state-space metrics with no number."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import spec, traffic
from test_new_cells import _rehearse, _run

CELL = "granite-4.0-h-small-serve.sessions-16k"
KANANA = "kanana-2-30b-a3b-serve.longdoc-32k"
NEW = {"ssm_busy_pct", "ssm_scan_busy_pct", "ssm_state_copy_busy_pct",
       "ssm_decode_roofline", "moe_held_assignments_pct",
       "ssm_state_held_gb"}
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


def test_the_cell_rehearses_through_the_state_group(rehearsed):
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
    assert probe["ssm_rows_live"] > 0 and probe["ssm_tokens"] > 0
    held = probe["moe_assignments_held"] / probe["moe_assignments"]
    assert 0.35 < held < 0.65
    # a rehearsal runs tiny widths: they are not the file's, and say so
    assert "mamba_d_state" in probe["differs_from_the_file"]
    small = spec.load_cell(CELL).config["probe"]["rehearsal"]
    assert probe["prefill"]["positions"] == small["prefill_rows"] + 1
    assert probe["decode"]["positions"] == small["answer_tokens"] - 1
    assert probe["prefill"]["worst"] < 1e-4 and probe["decode"]["worst"] < 1e-4
    # and of its STATE: what the sequence's last step left in its slot,
    # every state-space layer's against the reference's recurrence
    state = probe["state"]
    assert state["within"] and state["answered_alike"]
    assert state["layers"] == len(state["head_apart"]) == 3
    assert len(state["slow_heads_apart"]) == 3
    assert state["worst"] < 1e-4
    assert state["first_layer_slow_heads_apart"] < 1e-4


def test_the_cell_rehearses_untraced():
    p = _run(["--workload", CELL, "--rehearse"])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == 5 and last["rehearsal"] and not last["correct"]
    assert last["failed"] == 0 and "serve_tokens_per_s" in last["metrics"]


def test_the_new_metrics_read_the_scopes_the_role_and_the_records():
    import inspect

    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.models import mamba
    from megatron_llm_tpu.serving import loop_profiler

    cell = spec.load_cell(CELL)
    by_name = {m.name: m for m in cell.per_layer}
    assert NEW <= set(by_name)
    every = by_name["ssm_busy_pct"].params["scope"]
    assert every == ["ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_step",
                     "ssm_gate_norm", "ssm_out_proj"]
    assert by_name["ssm_scan_busy_pct"].params["scope"] == [
        "ssm_conv", "ssm_scan", "ssm_step"]
    assert set(every) | {"mamba"} <= set(hlo_collectives.SCOPES)
    opened = inspect.getsource(mamba.mamba_mixer)
    for scope in every:
        assert f'"{scope}"' in opened, scope
    copy = by_name["ssm_state_copy_busy_pct"]
    assert copy.source == "op_role_time" and copy.params["role"] == "ssm_state"
    assert "ssm_state" in inspect.getsource(hlo_collectives.ProgramTable)
    roof = by_name["ssm_decode_roofline"]
    assert roof.source == "ssm_roofline_share"
    assert roof.params["scopes"] == ["ssm_conv", "ssm_step"]
    assert loop_profiler.SSM_FIELDS == (
        "ssm_rows_live", "ssm_tokens", "ssm_state_bytes_held")
    assert "moe_assignments_held" in loop_profiler.MOE_FIELDS
    held = by_name["moe_held_assignments_pct"].params
    assert (held["numerator"], held["denominator"]) == (
        "moe_assignments_held", "moe_assignments")
    assert by_name["ssm_state_held_gb"].params["field"] == (
        "ssm_state_bytes_held")
    for name in NEW:
        body = json.load(open(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".json")))
        assert body["cells"] == [CELL], name


def test_the_scopes_and_the_role_reach_the_engines_instruction_tables():
    import jax

    from megatron_llm_tpu.models.granite import GraniteModel, granite_config
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    model = GraniteModel(granite_config("tiny", use_flash_attn=False))
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
                "ssm_out_proj", "moe_shared", "moe_route",
                "kv_write"} <= scopes, (name, scopes)


def test_the_file_is_the_catalogs_row_but_for_its_four_cuts():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"granite-4.0-h-small"' in ln)
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
    assert cfg["num_hidden_layers"] == 10
    assert cfg["layer_types"] == row["config"]["layer_types"][:10]
    assert cfg["layer_types"].count("attention") == 1
    assert cfg["num_local_experts"] == 36 and cfg["vocab_size"] == 50176
    assert len(entry["why"]) <= 200
    for said in ("ssm_state_dtype", "conv_state_layout"):
        assert said in cfg["assumed"], said
    assert "v5e-8" in cfg["deployment"]
    # every tolerance stands beside its readings
    assert "SOUND" in cfg["probe"]["margin_reason"]


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration():
    cell = spec.load_cell(CELL)
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"]) == (
        "closed_loop", 24, 0)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 2048,
                                  "max": 16384}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 128,
                                  "max": 1024}
    longdoc = spec.load_cell(KANANA).traffic
    for key in ("order_seed", "open_after_answers", "drain_seconds",
                "trace_seconds", "answer_timeout_seconds"):
        assert t[key] == longdoc[key], key
    # the strata and the cycle as longdoc-32k sets its own, by callers
    for key in ("documents_per_cycle", "strata_requests"):
        assert t[key] * longdoc["callers"] == longdoc[key] * t["callers"]
    src = traffic.ClosedLoopSource(t, 1, 50176)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    lengths = np.array([len(d.prompt) for d in docs])
    assert 6700 < lengths.mean() < 7100
    assert 415 < np.mean([d.answer_tokens for d in docs]) < 450
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert "--serve_max_model_len=17408" in flags and longest <= 17408
    assert max(max(d.prompt) for d in docs) < 50176
    # any 24 requests in a row of the order dealt fit the FULL pool, so
    # callers are served from the slots and not from the queue
    docs += [src.next() for _ in range(2 * t["documents_per_cycle"])]
    total = np.array([len(d.prompt) + d.answer_tokens for d in docs])
    in_flight = np.convolve(total, np.ones(24), "valid")
    blocks = int(next(f for f in flags if f.startswith(
        "--serve_num_blocks=")).split("=")[1])
    assert (blocks - 1) * 16 == 212992
    assert in_flight.max() <= (blocks - 1) * 16
    slots = int(next(f for f in flags if f.startswith(
        "--serve_num_slots=")).split("=")[1])
    assert t["callers"] == slots
    at = flags.index("--layer_types")
    assert flags[at + 1:at + 11] == cell.config["layer_types"]
    for flag in ("--model_name=granite", "--num_layers=10",
                 "--hidden_size=4096", "--num_attention_heads=32",
                 "--num_attention_heads_kv=8", "--kv_channels=128",
                 "--ffn_hidden_size=768", "--num_experts=36",
                 "--moe_router_experts=72", "--moe_experts_first=0",
                 "--moe_top_k=10", "--moe_shared_experts=2",
                 "--mamba_n_heads=128", "--mamba_d_head=64",
                 "--mamba_d_state=128", "--mamba_n_groups=1",
                 "--mamba_d_conv=4", "--mamba_chunk_size=256",
                 "--position_embedding_type=none",
                 "--attention_multiplier=0.0078125",
                 "--embedding_multiplier=12", "--residual_multiplier=0.22",
                 "--logits_scaling=16", "--layernorm_epsilon=1e-05",
                 "--vocab_size=50175", "--serve_prefill_chunk=512",
                 "--serve_block_size=16", "--serve_preemption=0"):
        assert flag in flags, flag
    cfg = cell.config
    assert cfg["shared_intermediate_size"] == 2 * cfg["intermediate_size"]
    assert cfg["mamba_n_heads"] * cfg["mamba_d_head"] == (
        cfg["mamba_expand"] * cfg["hidden_size"])
    # the probe: twelve chunks
    assert cfg["probe"]["prompt_tokens"] == 6144 == 12 * 512
    small = cfg["program"]["rehearsal_flags"]
    for flag in ("--moe_router_experts=8", "--num_experts=4",
                 "--position_embedding_type=none", "--mamba_d_state=16"):
        assert flag in small, flag
    bench = spec.load_benchmark()
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ())}
    assert NEW <= reported
    kananas = {m["name"] for m in bench["per_layer"]
               if KANANA in m.get("workloads", ())}
    assert reported - NEW == {n for n in kananas if not n.startswith("mla_")}
    assert not reported & {"decode_roofline", "prefill_roofline",
                           "moe_roofline"}
    served = next(m for m in bench["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert served["workloads"][-1] == CELL
    assert len(bench["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


@pytest.mark.parametrize("control", ["state_not_handed_on",
                                     "float8_activations"])
def test_a_fault_in_the_programs_place_fails_the_probe(control):
    """``granite_controls.py --control`` plants a fault in the program and
    runs the cell through the harness (rehearsed: float32, tiny): the
    probe's comparison of the ENGINE's logits reads it beyond a limit of
    the configuration file and the run's checks say so.  (Two controls
    are the chip's to show: ``state_bf16``, because against a float32
    rehearsal any rounding fails, which would prove nothing about the
    limit; ``scale_sqrt_head``, because a tiny model's scores are so
    small under either scale that its one attention layer in four moves
    the logits by 1e-4.  ``probe.margin_reason`` has both readings.)"""
    root = os.path.dirname(spec.BENCH_DIR)
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "granite_controls.py"),
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
