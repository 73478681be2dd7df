"""The cell PR 36 added, rehearsed on the CPU with its per-layer metrics:
``longdoc-32k`` prefills in chunks and decodes through the latent pool,
and prints the latent-attention metrics with no number."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from harness import spec, traffic
from test_new_cells import _rehearse, _run

CELL = "kanana-2-30b-a3b-serve.longdoc-32k"
KEYE = "keye-vl2-30b-a3b-serve.longdoc"
NEW = {"mla_busy_pct", "mla_expand_busy_pct", "moe_shared_busy_pct",
       "mla_decode_roofline", "mla_prefill_roofline"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


def test_the_cell_rehearses_through_the_latent_pool(rehearsed):
    last, lines = rehearsed
    assert not {"itl_p95_ms", "ttft_p50_ms", "moe_roofline",
                "prefill_roofline", "decode_roofline",
                "dsa_busy_pct"} & set(last["metrics"])
    # the probe's tight comparison is of the ENGINE's own logits over its
    # latent pool (the absorbed form), float32 in a rehearsal
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True
    assert probe["answered_alike"] and probe["tapped_every_row"]
    assert probe["step_token_deficit_worst"] == 0.0
    assert probe["mla_keys_live"] > 0 and probe["mla_pairs"] > 0
    # a rehearsal runs tiny widths: they are not the file's, and say so
    assert "kv_lora_rank" in probe["differs_from_the_file"]
    small = spec.load_cell(CELL).config["probe"]["rehearsal"]
    assert probe["prefill"]["positions"] == small["prefill_rows"] + 1
    assert probe["decode"]["positions"] == small["answer_tokens"] - 1
    assert probe["prefill"]["worst"] < 1e-4 and probe["decode"]["worst"] < 1e-4


def test_the_cell_rehearses_untraced():
    p = _run(["--workload", CELL, "--rehearse"])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == 5 and last["rehearsal"] and not last["correct"]
    assert last["failed"] == 0 and "serve_tokens_per_s" in last["metrics"]


def test_the_new_metrics_read_the_kernels_names_and_the_scopes():
    """The walk's share by the names the two reads launch under; the two
    scoped shares by scopes the program opens and its instruction tables
    know; the rooflines by the launch records' fields."""
    import inspect

    from harness.trace import op_family
    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.models import moe, transformer
    from megatron_llm_tpu.ops.pallas import paged_attention
    from megatron_llm_tpu.serving import loop_profiler

    cell = spec.load_cell(CELL)
    by_name = {m.name: m for m in cell.per_layer}
    assert NEW <= set(by_name)
    walk = re.compile(by_name["mla_busy_pct"].params["pattern"])
    decode = re.compile(by_name["mla_decode_roofline"].params["pattern"])
    chunk = re.compile(by_name["mla_prefill_roofline"].params["pattern"])
    ops = {"%mla_attention_decode.3": (True, True, False),
           "%mla_attention_prefill": (True, False, True),
           "%paged_attention_decode.12": (False, False, False),
           "%moe_experts.4": (False, False, False)}
    for op, want in ops.items():
        fam = op_family(op)
        assert tuple(bool(r.search(fam))
                     for r in (walk, decode, chunk)) == want, op
    source = inspect.getsource(paged_attention)
    assert ('name="mla_attention_decode"' in source
            and 'name="mla_attention_prefill"' in source)
    scopes = (by_name["mla_expand_busy_pct"].params["scope"]
              + by_name["moe_shared_busy_pct"].params["scope"])
    assert scopes == ["mla_expand", "mla_absorb", "moe_shared"]
    assert set(scopes) <= set(hlo_collectives.SCOPES)
    opened = inspect.getsource(transformer.latent_attention)
    assert ('named_scope("mla_absorb")' in opened
            and 'named_scope("mla_expand")' in opened)
    assert 'named_scope("moe_shared")' in inspect.getsource(moe._shared_mlp)
    assert loop_profiler.MLA_FIELDS == (
        "mla_keys_live", "mla_pairs", "mla_latents_expanded")
    for name in ("mla_decode_roofline", "mla_prefill_roofline"):
        assert by_name[name].source == "mla_roofline_share"


def test_the_scopes_reach_the_engines_instruction_tables():
    """A tiny engine's own programs, compiled here: instructions under
    ``mla_absorb`` and ``moe_shared`` are in the chunk's and the decode
    step's tables."""
    import jax

    from megatron_llm_tpu.models.kanana import KananaModel, kanana_config
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    model = KananaModel(kanana_config("tiny", use_flash_attn=False))
    eng = InferenceEngine(
        model, model.init(jax.random.PRNGKey(0)),
        EngineConfig(num_slots=2, block_size=16, max_model_len=64,
                     prefill_chunk=16))
    eng.warmup()
    for name, table in eng.program_tables().items():
        if name in ("engine_prefill", "engine_decode"):
            scopes = {r["scope"] for r in table.rows}
            assert {"mla_absorb", "moe_shared", "moe_route",
                    "kv_write"} <= scopes, (name, scopes)


def test_the_file_is_the_catalogs_row_but_for_its_depth():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"kanana-2-30b-a3b-instruct-2601"' in ln)
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 8 and len(entry["why"]) <= 200
    # every tolerance stands beside its readings
    assert "sound" in cfg["probe"]["margin_reason"]


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration():
    cell = spec.load_cell(CELL)
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"]) == (
        "closed_loop", 10, 0)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 8192,
                                  "max": 30720}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 128,
                                  "max": 512}
    longdoc = spec.load_cell(KEYE).traffic
    for key in ("order_seed", "open_after_answers", "drain_seconds",
                "trace_seconds"):
        assert t[key] == longdoc[key], key
    src = traffic.ClosedLoopSource(t, 1, 128256)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    lengths = np.array([len(d.prompt) for d in docs])
    assert 16500 < lengths.mean() < 17500
    assert 260 < np.mean([d.answer_tokens for d in docs]) < 295
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert "--serve_max_model_len=32768" in flags and longest <= 31232
    # any ten requests in a row of the order dealt fit the pool
    docs += [src.next() for _ in range(2 * t["documents_per_cycle"])]
    total = np.array([len(d.prompt) + d.answer_tokens for d in docs])
    in_flight = np.convolve(total, np.ones(10), "valid")
    blocks = int(next(f for f in flags if f.startswith(
        "--serve_num_blocks=")).split("=")[1])
    # (reserved in full; nine times in ten: a tenth request of the very
    # longest waits a few chunks for its pages, as at Keye's six)
    assert np.mean(in_flight <= (blocks - 1) * 16) > 0.9
    assert in_flight.mean() < 0.9 * (blocks - 1) * 16
    assert t["callers"] == (blocks - 1) * 16 // 17500 - 1
    for flag in ("--model_name=kanana", "--num_layers=8",
                 "--hidden_size=2048", "--num_attention_heads=32",
                 "--num_attention_heads_kv=32", "--ffn_hidden_size=6144",
                 "--moe_ffn_hidden_size=768", "--num_experts=128",
                 "--moe_top_k=6", "--moe_score_function=sigmoid",
                 "--moe_choice_bias=1", "--moe_routed_scale=2.448",
                 "--moe_shared_experts=2", "--moe_first_dense_layers=1",
                 "--kv_lora_rank=512", "--qk_nope_head_dim=128",
                 "--qk_rope_head_dim=64", "--v_head_dim=128",
                 "--vocab_size=128255", "--serve_num_slots=16",
                 "--serve_prefill_chunk=512"):
        assert flag in flags, flag
    # the probe: twelve chunks
    assert cell.config["probe"]["prompt_tokens"] == 6144 == 12 * 512
    # the rehearsal: one dense layer, a shared expert, a latent narrower
    # than the heads' total
    small = cell.config["program"]["rehearsal_flags"]
    for flag in ("--moe_first_dense_layers=1", "--moe_shared_experts=1",
                 "--kv_lora_rank=32", "--num_attention_heads=4",
                 "--qk_nope_head_dim=16", "--v_head_dim=16"):
        assert flag in small, flag
    bench = spec.load_benchmark()
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ())}
    assert NEW <= reported
    keyes = {m["name"] for m in bench["per_layer"]
             if KEYE in m.get("workloads", ())}
    assert reported - NEW == {n for n in keyes if not n.startswith("dsa_")}
    assert not reported & {"decode_roofline", "prefill_roofline",
                           "moe_roofline"}


@pytest.mark.parametrize("control", ["softmax_router", "no_shared",
                                     "float8_activations"])
def test_a_fault_in_the_programs_place_fails_the_probe(control):
    """``kanana_controls.py --control`` plants a fault in the program and
    runs the cell through the harness (rehearsed: float32, tiny): the
    probe's comparison of the ENGINE's logits reads it beyond a limit of
    the configuration file (the shared MLP left out at every tapped
    position; a tiny model's softmax router is close to its sigmoid one
    but for the slack, and float8 activations read by the medians) and
    the run's checks say so."""
    root = os.path.dirname(spec.BENCH_DIR)
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "kanana_controls.py"),
         "--control", control, "--", "--workload", CELL, "--seed", "7",
         "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[0] == {"note": "control", "planted": control}
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is False
    if control == "no_shared":
        for group in ("prefill", "decode"):
            assert len(probe[group]["beyond"]) == probe[group]["positions"]
    elif control == "softmax_router":
        assert probe["router_slack_worst"] > probe["router_slack_tolerance"]
    else:
        for group in ("prefill", "decode"):
            assert probe[group]["median"] > probe["tolerance"]
    checks = next(ln for ln in lines if ln.get("note") == "checks")
    assert checks["probe_within_margin_of_reference"] is False
    assert lines[-1]["correct"] is False
