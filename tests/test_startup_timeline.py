"""The start-up timeline of the two entry points, each in a process of
its own (the first stamp is taken before the entry module's imports, and
a process imports once): ``tools/run_text_generation_server.build_server``
at a tiny size and two steps of a tiny ``finetune.main()``, on the CPU.
Every span the timeline promises is there, the top-level ones are in
order and do not overlap, they cover at least 90% of ``[first stamp,
ready]``, the compile ledger's union inside a span never exceeds the
span, and the line, the JSONL record and ``engine.stats()['startup']``
carry the one summary."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REPORT = r"""
import json
from megatron_llm_tpu import tracing
tl = tracing.startup_timeline()
secs = tracing.CompileLedger.secs
print("TIMELINE " + json.dumps({
    "first": tl["first"], "ready": tl["ready"], "summary": tl["summary"],
    "spans": [[n, t0, t1, secs(tl["events"], t0, t1)]
              for n, t0, t1, _ in tl["spans"]],
    "top": [list(s[:3]) for s in tracing._top_level(tl["spans"])],
    "backend_union": secs(tl["events"], kinds=("backend",)),
    "trace_lower_union": secs(tl["events"], kinds=("trace", "lower")),
    "now": __import__("time").perf_counter(), "extra": extra}))
"""

_SERVER = r"""
import os, sys
sys.path.insert(0, os.path.join(ROOT, "tools"))
sys.path.insert(0, ROOT)
import run_text_generation_server as srv
from megatron_llm_tpu.initialize import initialize_megatron
argv = sys.argv[1:]
server = srv.build_server(initialize_megatron(
    extra_args_provider=srv.extra_args, args_list=argv), argv)
engine = server.generator.engine
from megatron_llm_tpu import tracing
extra = {"stats_is_summary":
         engine.stats()["startup"] == tracing.startup_summary()}
engine.stop()
"""

_TRAINER = r"""
import sys
sys.path.insert(0, ROOT)
import finetune
finetune.main()
extra = {}
"""

_TINY = ["--num_layers=2", "--hidden_size=64", "--num_attention_heads=4",
         "--seq_length=32", "--max_position_embeddings=64",
         "--micro_batch_size=1", "--global_batch_size=1"]

SERVER_SPANS = ["imports", "initialize", "build_model", "init_params",
                "shard_params", "engine_init", "warmup"]
SERVER_CHILDREN = ["runtime_init", "warmup.engine_prefill",
                   "warmup.engine_sample_first", "warmup.engine_decode",
                   "warmup.engine_cow_copy"]
TRAINER_SPANS = ["imports", "initialize", "build_model", "init_params",
                 "shard_params", "build_data", "build_optimizer",
                 "build_train_step", "first_step"]


def _run(body, argv, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, "-c", f"ROOT = {ROOT!r}\n" + body + _REPORT]
        + argv + [f"--structured_log_dir={tmp_path}"],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=280)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = out.stdout.splitlines()
    found = json.loads(next(l for l in lines if l.startswith("TIMELINE "))
                       [len("TIMELINE "):])
    found["lines"] = [l for l in lines if l.startswith(" [startup] ")]
    with open(os.path.join(str(tmp_path), "telemetry.jsonl")) as f:
        found["records"] = [r for r in map(json.loads, f)
                            if r.get("kind") == "startup"]
    return found


@pytest.mark.parametrize("entry", ["server", "trainer"])
def test_the_timeline_tiles_process_start_to_ready(entry, tmp_path):
    if entry == "server":
        found = _run(_SERVER, ["--model_name=llama2"] + _TINY + [
            "--tokenizer_type=NullTokenizer", "--vocab_size=127",
            "--serve_engine", "--serve_num_slots=2", "--serve_block_size=8",
            "--serve_max_model_len=64", "--serve_prefill_chunk=16",
            "--serve_alerts=0"], tmp_path)
        want, children = SERVER_SPANS, SERVER_CHILDREN
        assert found["extra"]["stats_is_summary"]
    else:
        found = _run(_TRAINER, ["--model_name=llama2"] + _TINY + [
            "--vocab_size=128", "--train_iters=2", "--lr=1e-4",
            "--log_interval=1"], tmp_path)
        want, children = TRAINER_SPANS, ["runtime_init"]
    summary, spans = found["summary"], found["spans"]
    first, ready = found["first"], found["ready"]
    # every promised span, top-level ones in the promised order
    top = found["top"]
    assert [s[0] for s in top] == want
    assert list(summary["spans"]) == want
    assert set(children) <= set(summary["children"])
    # one clock: perf_counter of that process, first stamp to ready
    assert spans[0][0] == "imports" and spans[0][1] == first
    assert first < ready <= found["now"]
    for a, b in zip(top, top[1:]):
        assert a[2] <= b[1], (a, b)                 # ordered, no overlap
    assert all(first <= s[1] <= s[2] <= ready for s in spans)
    # set-up's coverage: the top-level spans tile [first stamp, ready]
    covered = sum(summary["spans"].values())
    assert covered <= summary["wall_secs"] * (1 + 1e-6)
    assert covered >= 0.90 * summary["wall_secs"], summary
    assert summary["wall_secs"] == pytest.approx(ready - first, abs=1e-5)
    # union, not sum: the ledger inside a span never exceeds the span,
    # and trace + lower plus backend never exceed the whole
    for name, t0, t1, inside in spans:
        assert inside <= (t1 - t0) * (1 + 1e-6), name
    assert (found["trace_lower_union"] + found["backend_union"]
            <= summary["wall_secs"])
    assert summary["compile_secs"]["trace"] > 0.0
    assert summary["compile_secs"]["backend"] > 0.0
    # the programs that took the most tracing and lowering, by name
    programs = {p["program"]: p for p in summary["top_programs"]}
    assert len(programs) == 5
    mine = ("engine_prefill", "engine_decode") if entry == "server" \
        else ("train_step",)
    assert any(p in programs for p in mine), programs
    assert all(p["traced"] >= 1 and p["trace_lower_secs"] > 0.0
               for p in programs.values())
    # the one line and the one JSONL record carry the same
    assert len(found["lines"]) == 1 and len(found["records"]) == 1
    assert found["records"][0]["spans"] == summary["spans"]
    assert found["records"][0]["top_programs"] == summary["top_programs"]
    for name in want:
        assert f"{name} " in found["lines"][0]
