"""The two cells PR 26 added, rehearsed on the CPU with their per-layer
metrics: the routing metrics are printed (with no number) where the model
routes and left out where it does not, and the repeated documents are
docqa's loop with each document asked four times."""
import json

import pytest

from harness import spec, traffic
from test_rehearsal import _run

MOE = {"moe_experts_touched_pct", "moe_expert_imbalance"}


def _rehearse(cell):
    p = _run(["--workload", cell, "--rehearse", "--trace", "1"])
    assert p.returncode != 0, p.stdout[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["attempted"] > 0 and last["failed"] == 0
    assert all(m["value"] is None for m in last["metrics"].values())
    checks = next(ln for ln in lines if ln.get("note") == "checks")
    checks.pop("note")
    assert checks and all(v is True for v in checks.values()), checks
    return last, lines


def test_olmoe_chat_burst_rehearses_with_its_routing_metrics():
    last, lines = _rehearse("olmoe-1b-7b-serve.chat-burst")
    assert MOE <= set(last["metrics"])
    # the probe's second comparison: the program's logits (float32 in a
    # rehearsal) against the reference's at every position
    logits = next(ln for ln in lines if ln.get("note") == "probe_logits")
    assert logits["within"] is True and logits["logits_apart"] < 1e-4
    cell = spec.load_cell("olmoe-1b-7b-serve.chat-burst")
    t = cell.traffic_for_config()
    assert t["arrival_gaps"] == {"dist": "gamma", "shape": 0.25}
    assert t["requests_per_second"] == pytest.approx(
        0.8 * t["knee_requests_per_second"], rel=0.07)
    plan = traffic.open_loop_schedule(t, 45, 1, 50304)
    longest = max(len(r.prompt) + r.answer_tokens for r in plan)
    assert longest <= 2048 + 1024 <= cell.config["max_position_embeddings"]


def test_docqa_repeat_rehearses_and_asks_each_document_four_times():
    last, _ = _rehearse("mistral-7b-serve.docqa-repeat")
    assert "prefix_hit_pct" in last["metrics"]
    assert not MOE & set(last["metrics"])       # a dense model
    cell = spec.load_cell("mistral-7b-serve.docqa-repeat")
    docqa = spec.load_cell("mistral-7b-serve.docqa")
    assert cell.traffic["repeats"] == 4
    # docqa.json, its 12 callers too, but for the repeats (the file says
    # what the prefix cache makes of them: nothing, the cell's baseline)
    differs = ("repeats", "repeats_reason", "callers_reason", "who",
               "open_after_answers_reason", "rehearsal")
    assert {k: v for k, v in cell.traffic.items() if k not in differs} == \
        {k: v for k, v in docqa.traffic.items() if k not in differs}
    src = traffic.ClosedLoopSource(cell.traffic, 1, 32000)
    docs = [src.next() for _ in range(8)]
    for group in (docs[:4], docs[4:]):
        shared = min(len(d.prompt) for d in group)
        assert shared >= 2048
        assert all(d.prompt[:shared] == group[0].prompt[:shared]
                   for d in group)
    assert docs[0].prompt[:64] != docs[4].prompt[:64]
