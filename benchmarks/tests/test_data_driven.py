"""A later PR adds a configuration, a traffic mix (of a new KIND of
loop), a cell and a per-layer metric (from a new KIND of source) as NEW
files (and entries in BENCHMARK.json) and edits no file that is there;
run.py takes them."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha1(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_cell_as_files_only(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("megatron_llm_tpu", "tools", "finetune.py"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    before = _digest(os.path.join(root, "benchmarks"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = os.path.join(root, "benchmarks")

    # a configuration: the tiny Mixtral with another depth
    cfg = json.load(open(os.path.join(b, "configs", "mixtral-8x7b-serve.json")))
    cfg["program"]["rehearsal_flags"] = [
        f.replace("--num_layers=2", "--num_layers=3")
        for f in cfg["program"]["rehearsal_flags"]]
    json.dump(cfg, open(os.path.join(b, "configs", "new-config.json"), "w"))
    # a traffic mix: a closed loop that shares a prefix
    tr = json.load(open(os.path.join(b, "traffic", "docqa.json")))
    tr["rehearsal"]["shared_prefix_tokens"] = 32
    tr["rehearsal"]["callers"] = 2
    tr["kind"] = "new_loop"             # and a new KIND of loop drives it
    json.dump(tr, open(os.path.join(b, "traffic", "new-traffic.json"), "w"))
    with open(os.path.join(b, "loops", "new_loop.py"), "w") as f:
        f.write("from harness import spec\n\n\n"
                "def drive(run, driver, spec_t, vocab, trace_dir):\n"
                "    run.engine_settings['new_loop_drove'] = 1\n"
                "    spec.load_module('loops', 'closed_loop').drive(\n"
                "        run, driver, spec_t, vocab, trace_dir)\n")
    # a per-layer metric over an existing source
    json.dump({"unit": "ratio", "layer": "engine loop",
               "moves": "serve_tokens_per_s", "source": "counter_ratio",
               "params": {"num": ["tokens_generated"],
                          "den": ["decode_steps"]}},
              open(os.path.join(b, "layer_metrics", "new_metric.json"), "w"))
    # and a new KIND of source
    with open(os.path.join(b, "sources", "new_source.py"), "w") as f:
        f.write("def read(run, key):\n"
                "    return float(run.engine_settings[key])\n")
    json.dump({"unit": "rows", "layer": "scheduler and cache",
               "moves": "serve_tokens_per_s", "source": "new_source",
               "params": {"key": "new_loop_drove"}},
              open(os.path.join(b, "layer_metrics", "new_metric2.json"), "w"))

    cell = "new-config.new-traffic"
    bench["configs"].append({
        "name": "new-config", "source": "test",
        "file": "benchmarks/configs/new-config.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({"name": cell, "config": "new-config",
                               "traffic": "new-traffic", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(cell)
    for name, unit in (("new_metric", "ratio"), ("new_metric2", "rows")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "higher",
            "source": "program_counter", "layer": "engine loop",
            "moves": "serve_tokens_per_s", "workloads": [cell]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(b, "run.py"), "--workload", cell,
         "--rehearse", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert p.returncode != 0 and last["rehearsal"] is True
    assert {"new_metric", "new_metric2", "compile_s"} <= set(last["metrics"])
    assert "ttft_p90_ms.chat" not in last["metrics"]
    engine = next(json.loads(ln) for ln in p.stdout.splitlines()
                  if ln.startswith('{"note": "engine"'))
    assert engine["num_slots"] == 4
    after = _digest(os.path.join(root, "benchmarks"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/new-config.json", "traffic/new-traffic.json",
        "layer_metrics/new_metric.json", "layer_metrics/new_metric2.json",
        "sources/new_source.py", "loops/new_loop.py"}


def test_bare_benchmark_directory_prints_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and benchmarks/ the
    command fails and prints no result."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", bench["workloads"][0]["name"], "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and '"correct"' not in p.stdout
