"""The cell PR 61 added, rehearsed on the CPU with its per-layer metrics:
``glm-5-serve.longdoc-64k`` prefills in chunks and decodes through the
two-array latent pool under the indexer's choice, and prints the new
metrics with no number.  Its entries in ``BENCHMARK.json`` are found BY
NAME, not as the last ones: the next PR's append does not turn this file
red (``PERF.md`` open question 10 has what happened to the others)."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from harness import dsa_latent_roofline as cost
from harness import spec, traffic
from test_new_cells import _rehearse, _run

CELL = "glm-5-serve.longdoc-64k"
CONFIG = "glm-5-serve"
NEW = {"dsa_latent_busy_pct", "mla_query_busy_pct",
       "dsa_latent_decode_roofline", "dsa_latent_prefill_roofline"}
# accepted metrics whose readers read this family's keys right
TAKEN = {"dsa_selected_pct", "dsa_select_busy_pct", "dsa_select_counted_pct",
         "mla_busy_pct", "mla_expand_busy_pct", "moe_shared_busy_pct",
         "moe_held_assignments_pct", "moe_gated_held_roofline",
         "kv_pool_copy_busy_pct", "moe_routing_busy_pct",
         "moe_combine_busy_pct", "device_unattributed_pct"}
# and those that would misread it (per-head keys, ``sa_config``,
# ``intermediate_size`` for an expert's width, every LIVE latent counted)
LEFT = {"dsa_busy_pct", "dsa_decode_roofline", "dsa_prefill_roofline",
        "mla_decode_roofline", "mla_prefill_roofline", "moe_roofline",
        "decode_roofline", "prefill_roofline"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_the_cell_rehearses_through_the_selection_over_latents(rehearsed):
    last, lines = rehearsed
    assert not ({"itl_p95_ms", "ttft_p50_ms"} | LEFT) & set(last["metrics"])
    # the counters' metrics print (with no number, on a CPU)
    assert {"dsa_selected_pct", "dsa_select_counted_pct",
            "moe_held_assignments_pct"} <= set(last["metrics"])
    # the probe's tight comparison is of the ENGINE's own logits over its
    # pool of latent rows and indexer keys, float32 in a rehearsal
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True
    assert probe["answered_alike"] and probe["tapped_every_row"]
    assert probe["step_token_deficit_worst"] == 0.0
    assert probe["dsa_keys_live"] > probe["dsa_keys_selected"] > 0
    assert probe["mla_pairs"] > 0
    assert 0 < probe["moe_assignments_held"] < probe["moe_assignments"]
    # a rehearsal runs tiny widths: they are not the file's, and say so
    assert {"kv_lora_rank", "q_lora_rank", "index_topk"} <= set(
        probe["differs_from_the_file"])
    assert not {"index_query", "experts_first"} & set(
        probe["differs_from_the_file"])
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
        CONFIG, "longdoc-64k", 1)
    assert len(work["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/glm-5-serve.json"
    assert entry["reduced"] == cell.config["reduced"] == REDUCED
    reported = {m["name"]: m for m in bench["per_layer"]
                if CELL in m.get("workloads", ())}
    assert NEW | TAKEN <= set(reported) and not LEFT & set(reported)
    for name in NEW:
        m = reported[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "serve_tokens_per_s"
        assert m["source"] == "device_trace"
    ends = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", (CELL,))}
    assert ends == {"serve_tokens_per_s", "setup_s"}
    # one cell in four may take four chips: this one takes one
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_new_metrics_read_the_kernels_names_and_the_scopes(cell):
    """The selection's share by the names its six kernels launch under;
    the query's share by scopes the program opens and its instruction
    tables know; the rooflines by the launch records' fields."""
    import inspect

    from harness.trace import op_family
    from megatron_llm_tpu import hlo_collectives
    from megatron_llm_tpu.models import transformer
    from megatron_llm_tpu.ops.pallas import dsa_attention, paged_attention
    from megatron_llm_tpu.serving import loop_profiler

    by_name = {m.name: m for m in cell.per_layer}
    assert NEW <= set(by_name)
    busy = re.compile(by_name["dsa_latent_busy_pct"].params["pattern"])
    step = re.compile(by_name["dsa_latent_decode_roofline"].params["pattern"])
    chunk = re.compile(
        by_name["dsa_latent_prefill_roofline"].params["pattern"])
    walk = re.compile(by_name["mla_busy_pct"].params["pattern"])
    ops = {"%dsa_index_scores_decode.3": (True, True, False, False),
           "%dsa_select_decode": (True, True, False, False),
           "%mla_attention_sparse_decode.1": (True, True, False, True),
           "%dsa_index_scores_prefill": (True, False, True, False),
           "%dsa_select_prefill.2": (True, False, True, False),
           "%mla_attention_prefill_masked": (True, False, True, True),
           # Kanana's dense walks and Keye's masked ones are not this
           # selection's
           "%mla_attention_decode.3": (False, False, False, True),
           "%mla_attention_prefill": (False, False, False, True),
           "%paged_attention_sparse_decode": (False, False, False, False),
           "%moe_experts.4": (False, False, False, False)}
    for op, want in ops.items():
        fam = op_family(op)
        assert tuple(bool(r.search(fam))
                     for r in (busy, step, chunk, walk)) == want, op
    assert ('"mla_attention_prefill_masked"'
            in inspect.getsource(paged_attention))
    assert ('name="mla_attention_sparse_decode"'
            in inspect.getsource(dsa_attention))
    scopes = by_name["mla_query_busy_pct"].params["scope"]
    assert scopes == ["mla_query_down", "mla_query_up"]
    assert set(scopes) <= set(hlo_collectives.SCOPES)
    opened = inspect.getsource(transformer.latent_attention)
    for scope in scopes + ["dsa_indexer"]:
        assert f'named_scope("{scope}")' in opened
    assert set(loop_profiler.DSA_FIELDS) >= {"dsa_keys_live",
                                             "dsa_keys_selected"}
    for name in ("dsa_latent_decode_roofline", "dsa_latent_prefill_roofline"):
        assert by_name[name].source == "dsa_latent_roofline_share"


def test_the_scopes_reach_the_engines_instruction_tables():
    """A tiny engine's own programs, compiled here: instructions under
    the two query scopes, the indexer's and the absorbed form's are in
    the chunk's and the decode step's tables, and none is without a
    role."""
    import jax

    from megatron_llm_tpu.models.glm5 import Glm5Model, glm5_config
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

    model = Glm5Model(glm5_config("tiny", use_flash_attn=False))
    eng = InferenceEngine(
        model, model.init(jax.random.PRNGKey(0)),
        EngineConfig(num_slots=2, block_size=16, max_model_len=64,
                     prefill_chunk=16))
    eng.warmup()
    for name, table in eng.program_tables().items():
        if name in ("engine_prefill", "engine_decode"):
            scopes = {r["scope"] for r in table.rows}
            assert {"mla_query_down", "mla_query_up", "dsa_indexer",
                    "mla_absorb", "moe_shared", "moe_route",
                    "kv_write"} <= scopes, (name, scopes)


def test_the_rooflines_arithmetic_is_the_issues(cell):
    """What no implementation could avoid, at the published widths: 256 B
    a live position and 1,152 B a selected one, 139,264 operations a
    selected row (decode); 65,536 operations an attended pair and 8,192 a
    scored one (a chunk), all layers; never more than the dense count."""
    cfg = cell.config
    assert cost.index_key_bytes(cfg) == 256 and cost.row_bytes(cfg) == 1152
    assert cost.absorbed_flops_per_row(cfg) == 139264
    assert cost.expanded_flops_per_pair(cfg) == 65536
    assert cost.score_flops_per_pair(cfg) == 8192
    # a decode launch: 8 rows of 40,000 keys, 5 layers
    live, chosen = 8 * 40000 * 5, 8 * 2048 * 5
    flops, nbytes = cost.decode_cost(cfg, live, chosen)
    assert flops == chosen * 139264
    assert nbytes == live * 256 + chosen * 1152
    least = cost.decode_least_seconds(cfg, live, chosen, PEAKS)
    assert least == pytest.approx(max(flops / 197e12, nbytes / 819e9))
    assert least == pytest.approx(nbytes / 819e9)     # the bytes bind
    # a chunk of 512 at a context of 20,480: every query attends 2,048
    flops, nbytes = cost.prefill_cost(cfg, 20480, 512)
    seen = sum(20480 + j + 1 for j in range(512))
    assert flops == 5 * (512 * 2048 * 65536 + seen * 8192)
    assert nbytes == 5 * (20992 * 256 + 2048 * 1152)
    # and from an empty context: query j attends j + 1
    flops, _ = cost.prefill_cost(cfg, 0, 512)
    pairs = 512 * 513 // 2
    assert flops == 5 * pairs * (65536 + 8192)
    # under the selection a chunk costs less than the dense pairs would
    dense = 5 * seen * 65536
    assert cost.prefill_cost(cfg, 20480, 512)[0] < 0.25 * dense
    assert cost.prefill_least_seconds(cfg, 20480, 512, PEAKS) == (
        pytest.approx(cost.prefill_cost(cfg, 20480, 512)[0] / 197e12))


def test_the_roofline_share_reads_nothing_where_there_is_nothing():
    """The source on runs that lack what it reads (no trace, another
    family's configuration, records without the fields): None, never an
    error, so the parent's line leaves the metric out."""
    import types

    share = spec.load_module("sources", "dsa_latent_roofline_share")
    run = types.SimpleNamespace(trace=None, peaks=PEAKS,
                                setup_parts={"traced": (0.0, 1.0)})
    assert share.read(run, "decode", "^dsa_") is None
    run.trace = types.SimpleNamespace(op_seconds=lambda pattern: 0.0)
    run.setup_parts = {}
    assert share.read(run, "prefill", "^dsa_") is None
    keye = spec.load_cell("keye-vl2-30b-a3b-serve.longdoc")
    run = types.SimpleNamespace(cell=keye, model_shape={}, peaks=PEAKS)
    assert share.least_total(run, [], "decode") is None
    glm = types.SimpleNamespace(cell=spec.load_cell(CELL), model_shape={},
                                peaks=PEAKS)
    bare = [types.SimpleNamespace(start=0, valid=4)]
    assert share.least_total(glm, bare, "prefill") is None
    rec = types.SimpleNamespace(start=0, valid=4, dsa_keys_live=50,
                                dsa_keys_selected=50)
    assert share.least_total(glm, [rec], "prefill") > 0
    assert share.least_total(glm, [rec], "decode") == pytest.approx(
        50 * (256 + 1152) / 819e9)
    idle = types.SimpleNamespace(start=0, valid=0, dsa_keys_live=0,
                                 dsa_keys_selected=0)
    assert share.least_total(glm, [idle], "decode") is None


def test_the_file_is_the_catalogs_row_but_for_its_four_cuts(cell):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"name": "GLM-5"' in ln)
    cfg = cell.config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert cfg["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880}
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 16, 19456)
    # the floors: four layers after the dense ones, 8 experts, an eighth
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["vocab_size"] * 8 >= 154880 and cfg["n_routed_experts"] >= 8
    assert cfg["num_nextn_predict_layers"] == 1 and "mtp" in cfg["assumed"]
    for key in ("deployment", "bytes", "assumed", "published"):
        assert cfg[key], key
    # every tolerance stands beside its readings
    assert "sound" in cfg["probe"]["margin_reason"]


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration(cell):
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"]) == (
        "closed_loop", 8, 0)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 16384,
                                  "max": 65536}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 128,
                                  "max": 512}
    assert (t["open_after_answers"], t["documents_per_cycle"],
            t["strata_requests"], t["trace_seconds"], t["drain_seconds"],
            t["answer_timeout_seconds"]) == (2, 24, 8, 3, 300, 900)
    others = {spec.load_cell(w["name"]).traffic.get("order_seed")
              for w in spec.load_benchmark()["workloads"]
              if w["name"] != CELL}
    assert t["order_seed"] not in others
    src = traffic.ClosedLoopSource(t, 1, 19456)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    lengths = np.array([len(d.prompt) for d in docs])
    assert 34000 < lengths.mean() < 37000
    assert 16384 <= lengths.min() and lengths.max() <= 65536
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert "--serve_max_model_len=66560" in flags and longest <= 66048
    assert max(max(d.prompt) for d in docs) < 19456
    # the pool holds nine documents of the mean with their answers: eight
    # callers are served from it
    blocks = int(next(f for f in flags if f.startswith(
        "--serve_num_blocks=")).split("=")[1])
    assert (blocks - 1) * 16 == 327680
    assert t["callers"] == (blocks - 1) * 16 // 36000 - 1
    for flag in ("--model_name=glm5", "--num_layers=5", "--hidden_size=6144",
                 "--num_attention_heads=64", "--num_attention_heads_kv=64",
                 "--ffn_hidden_size=12288", "--moe_ffn_hidden_size=2048",
                 "--num_experts=16", "--moe_router_experts=256",
                 "--moe_experts_first=0", "--moe_top_k=8",
                 "--moe_score_function=sigmoid", "--moe_choice_bias=1",
                 "--moe_routed_scale=2.5", "--moe_shared_experts=1",
                 "--moe_first_dense_layers=1", "--kv_lora_rank=512",
                 "--q_lora_rank=2048", "--qk_nope_head_dim=192",
                 "--qk_rope_head_dim=64", "--v_head_dim=256",
                 "--dsa_index_heads=32", "--dsa_index_head_dim=128",
                 "--dsa_index_rope_dim=64", "--dsa_index_query=compressed",
                 "--dsa_topk=2048", "--rope_theta=1000000",
                 "--layernorm_epsilon=1e-05", "--vocab_size=19455",
                 "--serve_num_slots=10", "--serve_block_size=16",
                 "--serve_prefill_chunk=512", "--serve_preemption=0"):
        assert flag in flags, flag
    # the flags carry the file's widths
    cfg = cell.config
    for key, flag in (("hidden_size", "hidden_size"),
                      ("intermediate_size", "ffn_hidden_size"),
                      ("moe_intermediate_size", "moe_ffn_hidden_size"),
                      ("kv_lora_rank", "kv_lora_rank"),
                      ("q_lora_rank", "q_lora_rank"),
                      ("index_n_heads", "dsa_index_heads"),
                      ("index_head_dim", "dsa_index_head_dim"),
                      ("index_topk", "dsa_topk"),
                      ("n_routed_experts", "num_experts"),
                      ("num_experts_per_tok", "moe_top_k")):
        assert f"--{flag}={cfg[key]}" in flags, key
    assert f"--moe_router_experts={cfg['published']['n_routed_experts']}" \
        in flags
    # the probe: twelve chunks, so 2,048 of some 6,150 latents are chosen
    assert cfg["probe"]["prompt_tokens"] == 6144 == 12 * 512
    assert cfg["probe"]["prompt_tokens"] > 2 * cfg["index_topk"]
    # the rehearsal: a top-k under every rehearsed context, an indexer
    # head of which half rotates, a share of the experts
    small = cfg["program"]["rehearsal_flags"]
    for flag in ("--dsa_topk=16", "--dsa_index_head_dim=16",
                 "--dsa_index_rope_dim=8", "--dsa_index_query=compressed",
                 "--q_lora_rank=48", "--num_experts=4",
                 "--moe_router_experts=8", "--serve_prefill_chunk=32"):
        assert flag in small, flag
    assert t["rehearsal"]["prompt_tokens"]["min"] > 16


@pytest.mark.parametrize("control", [
    "dense", "topk_half", "unweighted", "index_query_from_input",
    "no_query_norm", "index_no_rope", "bias_in_gates",
    "float8_activations"])
def test_a_fault_in_the_programs_place_fails_the_probe(control):
    """``glm5_controls.py --control`` plants a fault in the program and
    runs the cell through the harness (rehearsed: float32, tiny): the
    probe's comparison of the ENGINE's logits reads it beyond a limit of
    the configuration file and the run's checks say so."""
    root = os.path.dirname(spec.BENCH_DIR)
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "glm5_controls.py"),
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
