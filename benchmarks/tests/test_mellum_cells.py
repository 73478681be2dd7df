"""The cell PR 32 added, rehearsed on the CPU with its per-layer metrics:
``repo-mixed`` walks both groups of pages (its rehearsed contexts pass
the rehearsal's window and give pages back) and prints the metrics of the
two groups with no number."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import spec, traffic
from test_new_cells import _rehearse, _run

CELL = "mellum2-12b-a2.5b-serve.repo-mixed"
KEYE = "keye-vl2-30b-a3b-serve.longdoc"
COUNTED = {"kv_window_pages_returned_pct", "kv_held_bytes_per_token"}
TRACED = {"attention_window_busy_pct", "attention_full_busy_pct"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def rehearsed():
    return _rehearse(CELL)


def test_the_cell_rehearses_through_both_groups(rehearsed):
    last, lines = rehearsed
    # the counters' metrics are printed wherever the program keeps the
    # fields; the trace's metrics have nothing to read on the CPU
    assert COUNTED <= set(last["metrics"])
    assert not {"itl_p95_ms", "ttft_p50_ms", "moe_roofline",
                "prefill_roofline", "dsa_busy_pct"} & set(last["metrics"])
    # the probe's tight comparison is of the ENGINE's own logits over its
    # two-group pool, float32 in a rehearsal, pages given back before it
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is True and probe["pattern_is_the_files"] is True
    assert probe["answered_alike"] and probe["tapped_every_row"]
    assert probe["step_token_deficit_worst"] == 0.0
    assert probe["window_pages_returned"] > 0
    small = spec.load_cell(CELL).config["probe"]["rehearsal"]
    assert probe["prefill"]["positions"] == small["prefill_rows"] + 1
    assert probe["decode"]["positions"] == small["answer_tokens"] - 1
    assert probe["prefill"]["worst"] < 1e-4 and probe["decode"]["worst"] < 1e-4


def test_the_cell_rehearses_untraced():
    p = _run(["--workload", CELL, "--rehearse"])
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode == 5 and last["rehearsal"] and not last["correct"]
    assert last["failed"] == 0 and "serve_tokens_per_s" in last["metrics"]


def test_the_new_metrics_read_the_launch_records_and_the_kernels_names():
    """The two counted metrics from launch records with and without the
    fields (the parent's have none: nothing to read, no error); the two
    traced ones by the names the window and the full group launch under."""
    import re
    import types

    ratio = spec.load_module("sources", "loop_record_ratio")
    rec = types.SimpleNamespace
    new = [rec(kv_window_pages_returned=30, kv_window_pages_spanned=32,
               kv_held_bytes=9000, kv_live_tokens=2),
           rec(kv_window_pages_returned=10, kv_window_pages_spanned=8,
               kv_held_bytes=3000, kv_live_tokens=1)]
    assert ratio.sums(new, "kv_window_pages_returned",
                      "kv_window_pages_spanned") == (40, 40)
    assert ratio.sums(new, "kv_held_bytes", "kv_live_tokens") == (12000, 3)
    assert ratio.sums([rec(rows=1)], "kv_held_bytes",
                      "kv_live_tokens") is None
    from harness.trace import op_family

    cell = spec.load_cell(CELL)
    by_name = {m.name: m for m in cell.per_layer}
    assert by_name["kv_held_bytes_per_token"].params == {
        "numerator": "kv_held_bytes", "denominator": "kv_live_tokens"}
    window = re.compile(by_name["attention_window_busy_pct"].params["pattern"])
    full = re.compile(by_name["attention_full_busy_pct"].params["pattern"])
    ops = {"%paged_attention_decode_window.3": (True, False),
           "%paged_attention_prefill_window": (True, False),
           "%paged_attention_decode.12": (False, True),
           "%paged_attention_prefill.1": (False, True),
           "%paged_attention_prefill_masked.2": (False, False),
           "%paged_attention_decode_quant": (False, False),
           "%moe_experts.4": (False, False)}
    for op, (w, f) in ops.items():
        fam = op_family(op)
        assert (bool(window.search(fam)), bool(full.search(fam))) == (w, f), op
    # the program launches under exactly these names
    from megatron_llm_tpu.ops import paged_kv
    import inspect

    assert '"_window" if self.group == WINDOW' in inspect.getsource(
        paged_kv.PagedKVCache.attend)


def test_the_file_is_the_catalogs_row_but_for_what_it_reduces():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"Mellum2-12B-A2.5B-Instruct"' in ln)
    cell = spec.load_cell(CELL)
    cfg = cell.config
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == cell.config_name)
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # two whole periods of the published pattern
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == row["config"]["layer_types"][:8]
    assert cfg["layer_types"][:4] * 2 == cfg["layer_types"]
    assert cfg["mlp_layer_types"] == ["sparse"] * 8


def test_the_cell_is_the_issues_traffic_and_fits_the_configuration():
    cell = spec.load_cell(CELL)
    t, flags = cell.traffic, cell.config["program"]["flags"]
    assert (t["kind"], t["callers"], t["shared_prefix_tokens"]) == (
        "closed_loop", 24, 0)
    assert t["prompt_tokens"] == {"dist": "loguniform", "min": 512,
                                  "max": 32768}
    assert t["answer_tokens"] == {"dist": "loguniform", "min": 64,
                                  "max": 512}
    longdoc = spec.load_cell(KEYE).traffic
    assert t["order_seed"] == longdoc["order_seed"]
    src = traffic.ClosedLoopSource(t, 1, 98304)
    docs = [src.next() for _ in range(t["documents_per_cycle"])]
    lengths = np.array([len(d.prompt) for d in docs])
    assert 7500 < lengths.mean() < 8000 and 3900 < np.median(lengths) < 4300
    assert 200 < np.mean([d.answer_tokens for d in docs]) < 230
    longest = max(len(d.prompt) + d.answer_tokens for d in docs)
    assert "--serve_max_model_len=33792" in flags and longest <= 33792
    # three requests in four pass the window group's bound
    bound = 1024 + 512 + 16
    assert 0.7 < (lengths > bound).mean() < 0.8
    # any 24 requests in a row of the order dealt fit the full group
    # (three cycles: 130k to 262k tokens reserved, mean 194k), and would
    # not fit the 135k tokens one table a slot would hold in its bytes
    docs += [src.next() for _ in range(2 * t["documents_per_cycle"])]
    total = np.array([len(d.prompt) + d.answer_tokens for d in docs])
    in_flight = np.convolve(total, np.ones(24), "valid")
    assert in_flight.max() <= 24576 * 16
    assert np.mean(in_flight > 135_000) > 0.9
    for flag in ("--hidden_size=2304", "--num_attention_heads=32",
                 "--num_attention_heads_kv=4", "--kv_channels=128",
                 "--num_experts=64", "--moe_top_k=8",
                 "--moe_ffn_hidden_size=896", "--ffn_hidden_size=7168",
                 "--sliding_window_size=1024", "--vocab_size=98303",
                 "--serve_num_slots=32", "--serve_prefill_chunk=512",
                 "--serve_num_blocks=24577", "--num_layers=8"):
        assert flag in flags, flag
    at = flags.index("--layer_types")
    assert flags[at + 1:at + 5] == ["sliding", "sliding", "sliding", "full"]
    at = flags.index("--rope_yarn_scaling")
    yarn = cell.config["rope_parameters"]["full_attention"]
    assert [float(x) for x in flags[at + 1:at + 6]] == [
        yarn["factor"], yarn["original_max_position_embeddings"],
        yarn["beta_fast"], yarn["beta_slow"], yarn["attention_factor"]]
    # the probe: twelve chunks, so that pages have gone back before every
    # compared position
    assert cell.config["probe"]["prompt_tokens"] == 6144 == 12 * 512
    # the rehearsal's contexts pass its window group's bound
    small = cell.config["program"]["rehearsal_flags"]
    assert "--sliding_window_size=16" in small
    assert t["rehearsal"]["prompt_tokens"]["min"] >= 16 + 16
    bench = spec.load_benchmark()
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ())}
    assert COUNTED | TRACED <= reported
    keyes = {m["name"] for m in bench["per_layer"]
             if KEYE in m.get("workloads", ())}
    assert reported - COUNTED - TRACED == {
        n for n in keyes if not n.startswith("dsa_")}
    assert not reported & {"decode_roofline", "prefill_roofline",
                           "moe_roofline"}


def test_a_fault_in_the_programs_place_fails_the_probe():
    """``mellum_controls.py --control all_full`` makes every layer attend
    every key and runs the cell through the harness (rehearsed: float32,
    tiny): the probe's comparison of the ENGINE's logits reads every
    tapped position beyond its tolerance and the run's checks say so."""
    root = os.path.dirname(spec.BENCH_DIR)
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "reference",
                                      "mellum_controls.py"),
         "--control", "all_full", "--", "--workload", CELL, "--seed", "7",
         "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[0] == {"note": "control", "planted": "all_full"}
    probe = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert probe["within"] is False
    for group in ("prefill", "decode"):
        assert len(probe[group]["beyond"]) == probe[group]["positions"]
        assert min(probe[group]["apart"]) > 3 * probe["tolerance"]
    checks = next(ln for ln in lines if ln.get("note") == "checks")
    assert checks["probe_within_margin_of_reference"] is False
    assert lines[-1]["correct"] is False


def test_the_probe_gives_the_engines_experts_at_every_tapped_row(monkeypatch):
    """``keye_probe.py::engine_against_reference`` gives a row its experts
    only where the reference's first pass chose others, and a row that
    agreed can fall the other way in the second (the chip, seed
    213089078, position 6148).  ``mellum_probe.EveryRowGiven`` hands the
    reference, in that second pass, the first pass's own experts at the
    rows that agreed, and what was given at the others."""
    probe = spec.load_module("reference", "mellum_probe")
    assert isinstance(probe.shared.plain, probe.EveryRowGiven)
    rows = [4, 9, 10]
    own = [(np.arange(12 * 2).reshape(12, 2) + 100 * i, np.zeros(12))
           for i in range(3)]
    calls = []

    def forward_logits(weights, cfg, tokens, rows=None, routing=None,
                       forced=None, **more):
        calls.append({"forced": forced, **more})
        if routing is not None:
            routing.extend(own)
        return "logits"

    monkeypatch.setattr(probe.plain, "forward_logits", forward_logits)
    ref = probe.EveryRowGiven()
    assert ref.position_losses is probe.plain.position_losses
    record = []
    assert ref.forward_logits("w", {}, [0] * 12, rows=np.asarray(rows),
                              routing=record, faults=frozenset()) == "logits"
    assert record == own and calls[0]["forced"] is None
    # the engine's experts differ at row 9, in layers 0 and 2's records
    given = {i: {9: [7, 8]} for i in range(3)}
    ref.forward_logits("w", {}, [0] * 12, rows=np.asarray(rows),
                       forced=given, faults=frozenset())
    assert calls[1]["forced"] == {
        i: {4: own[i][0][4].tolist(), 9: [7, 8], 10: own[i][0][10].tolist()}
        for i in range(3)}
    assert calls[1]["faults"] == frozenset()
