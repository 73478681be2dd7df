"""The two cells PR 30 added, rehearsed on the CPU with their per-layer
metrics: ``longdoc`` walks the selection (its rehearsed contexts exceed
the rehearsal's top-k) and prints the sparse-attention metrics with no
number; ``mixed`` is chat's open loop with a tail of long prompts."""
import json

import numpy as np
import pytest

from harness import spec, traffic
from test_new_cells import _rehearse

LONGDOC = "keye-vl2-30b-a3b-serve.longdoc"
MIXED = "mistral-7b-serve.mixed"
DSA = {"dsa_busy_pct", "dsa_select_busy_pct", "dsa_selected_pct",
       "dsa_decode_roofline", "dsa_prefill_roofline"}


def test_longdoc_rehearses_through_the_selection():
    last, lines = _rehearse(LONGDOC)
    # the counters' metric is printed wherever the program keeps the
    # fields; the trace's metrics have nothing to read on the CPU
    assert "dsa_selected_pct" in last["metrics"]
    # the cell judges no gap between tokens (six runs spread 1.6% in
    # itl_p95_ms), so nothing that moves it is reported here
    assert not {"itl_p95_ms", "moe_experts_touched_pct",
                "decode_program_ms"} & set(last["metrics"])
    # the probe's tight comparison is of the ENGINE's own logits (its
    # prefill program's and its decode step's, over its pool), float32 in
    # a rehearsal: every tapped position, the answer positions among them
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True and probe["topk_is_the_files"] is True
    assert probe["answered_alike"] is True
    assert probe["step_token_deficit_worst"] == 0.0
    small = spec.load_cell(LONGDOC).config["probe"]["rehearsal"]
    assert probe["prefill"]["positions"] == small["prefill_rows"] + 1
    assert probe["decode"]["positions"] == small["answer_tokens"] - 1
    assert probe["prefill"]["worst"] < 1e-4 and probe["decode"]["worst"] < 1e-4
    cell = spec.load_cell(LONGDOC)
    flags = cell.config["program"]["rehearsal_flags"]
    topk = int(next(f for f in flags if f.startswith("--dsa_topk=")
                    ).split("=")[1])
    assert topk == probe["topk"] == cell.config["probe"]["rehearsal"]["topk"]
    # every rehearsed context exceeds the rehearsal's top-k
    assert cell.traffic["rehearsal"]["prompt_tokens"]["min"] > topk
    assert cell.config["probe"]["rehearsal"]["prompt_tokens"] > topk


def test_longdoc_is_the_issues_traffic_and_fits_the_configuration():
    cell = spec.load_cell(LONGDOC)
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert (t["kind"], t["callers"], t["documents_per_cycle"],
            t["open_after_answers"]) == ("closed_loop", 6, 18, 2)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 8192,
                                  "max": 32768}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 128,
                                  "max": 512}
    src = traffic.ClosedLoopSource(t, 1, 151936)
    docs = [src.next() for _ in range(18)]
    lengths = [len(d.prompt) for d in docs]
    assert 17000 < np.mean(lengths) < 18500
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert "--serve_max_model_len=33792" in flags and longest <= 33792
    # the probe passes THROUGH the selection: three times the top-k
    assert cell.config["probe"]["prompt_tokens"] == 6144 > \
        2 * cell.config["sa_config"]["topk"]
    # the published widths, with only the depth reduced
    assert cell.config["reduced"] == ["num_hidden_layers"]
    for flag in ("--hidden_size=2048", "--num_attention_heads=32",
                 "--num_attention_heads_kv=4", "--kv_channels=128",
                 "--num_experts=128", "--moe_top_k=8",
                 "--moe_ffn_hidden_size=768", "--ffn_hidden_size=6144",
                 "--dsa_index_heads=16", "--dsa_index_head_dim=64",
                 "--dsa_topk=2048", "--vocab_size=151935",
                 "--serve_prefill_chunk=512", "--serve_num_blocks=8193"):
        assert flag in flags, flag
    bench = spec.load_benchmark()
    reported = {m["name"] for m in bench["per_layer"]
                if LONGDOC in m.get("workloads", ())}
    assert DSA <= reported
    # not the shares whose arithmetic does not know this model
    assert not reported & {"decode_roofline", "prefill_roofline",
                           "moe_roofline", "moe_expert_imbalance",
                           "decode_attention_busy_pct"}


def test_mixed_rehearses_and_is_chats_loop_with_long_prompts():
    last, _ = _rehearse(MIXED)
    # judged on tokens a second alone: six runs of the parent spread 16%
    # in ttft_p50_ms and 0.8% in itl_p95_ms, over a fifth of their bounds,
    # so the first token's wait is read as a per-layer metric
    assert "ttft_p50_ms.docqa" in last["metrics"]
    assert not {"ttft_p50_ms", "itl_p95_ms", "ttft_prefill_own_p50_ms",
                "decode_program_ms"} & set(last["metrics"])
    assert not DSA & set(last["metrics"])         # a model with no indexer
    cell = spec.load_cell(MIXED)
    chat = spec.load_cell("mistral-7b-serve.chat")
    t = cell.traffic_for_config()
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                  "sigma": 1.6, "min": 32, "max": 7936}
    for key in ("kind", "answer_tokens", "arrival_gaps", "lead_in_seconds",
                "order_seed", "shared_prefix_tokens"):
        assert cell.traffic[key] == chat.traffic[key], key
    assert t["requests_per_second"] == pytest.approx(
        0.8 * t["knee_requests_per_second"], rel=0.05)
    plan = traffic.open_loop_schedule(t, 45, 1, 32000)
    prompts = np.array([len(r.prompt) for r in plan])
    longest = max(len(r.prompt) + r.answer_tokens for r in plan)
    assert longest <= 7936 + 512 == 8448
    assert 0.05 < (prompts > 2048).mean() < 0.15
    assert cell.config == chat.config             # the configuration unchanged


def test_a_fault_in_the_programs_place_fails_the_probe():
    """``keye_controls.py --control dense`` plants dense attention in the
    selection's place and runs the cell through the harness (rehearsed:
    float32, tiny): the probe's comparison of the ENGINE's logits reads
    every tapped position beyond its tolerance and the run's checks say
    so."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(spec.BENCH_DIR)
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "keye_controls.py"),
         "--control", "dense", "--", "--workload", LONGDOC, "--seed", "7",
         "--seconds", "5", "--trace", "0", "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[0] == {"note": "control", "planted": "dense"}
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is False and probe["topk_is_the_files"] is True
    assert probe["answered_alike"] and probe["tapped_every_row"]
    for group in ("prefill", "decode"):
        assert len(probe[group]["beyond"]) == probe[group]["positions"]
        assert min(probe[group]["apart"]) > 3 * probe["tolerance"]
    checks = next(ln for ln in lines if ln.get("note") == "checks")
    assert checks["probe_within_margin_of_reference"] is False
    assert lines[-1]["correct"] is False


def test_the_taps_tell_a_rows_experts_from_one_row_records():
    """A decode step's record with one live row, a chunk's of one live
    row, and a chunk's less the same chunk's one row shorter each give
    that row's experts; anything else gives None."""
    probe = spec.load_module("reference", "keye_probe")
    taps = probe.Taps.__new__(probe.Taps)
    taps.routing, taps.chunk = {}, {}
    row = np.zeros((2, 8), np.int64)
    row[0, [1, 4]] = 1
    row[1, [0, 7]] = 1
    taps.routing[50] = row
    assert taps.experts(50, 2) == [[1, 4], [0, 7]]
    assert taps.experts(50, 3) is None and taps.experts(51, 2) is None
    taps.chunk[32] = (32, row)                  # one live row
    assert taps.experts(32, 2) == [[1, 4], [0, 7]]
    longer = row * 3
    longer[0, 2] += 1
    longer[0, 4] += 1
    longer[1, [5, 6]] += 1
    taps.chunk[38] = (32, row * 3)              # rows 32..38
    taps.chunk[39] = (32, longer)               # rows 32..39
    assert taps.experts(39, 2) == [[2, 4], [5, 6]]
    assert taps.experts(38, 2) is None          # no record one row shorter
    taps.chunk[38] = (16, row * 3)              # another chunk's
    assert taps.experts(39, 2) is None
