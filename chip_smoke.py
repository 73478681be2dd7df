#!/usr/bin/env python3
"""Chip smoke: the trainer and the serving engine, once, on a TPU.

    python chip_smoke.py              # one chip: trainer, then server
    python chip_smoke.py --chips 4    # four chips: the trainer on a
                                      # tp2 x dp2 mesh and the same steps
                                      # on one chip — no other phase
    python chip_smoke.py --rehearse [--chips 4]
                                      # tiny sizes on the CPU: walks the
                                      # same code, can never pass

Both phases go through the entry points a user calls — ``finetune.py``'s
``main()`` and ``tools/run_text_generation_server.py``'s server over
HTTP — at Mistral-7B widths (hidden 4096, 32 heads, 8 kv heads, ffn
14336, vocab 32000, seq 4096, bf16) with random weights made from
``--seed``.  Only the depth is cut, to what one 16 GB chip holds (see
``TRAIN_LAYERS`` / ``SERVE_LAYERS``).

Processes: this file is a parent that never imports JAX and runs each
phase in a child of its own, one after another — a chip belongs to one
process at a time, and a phase that starts clean reports its own peak
memory.  Every child prints one JSON record; the parent's LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and it exits 0 only if every phase passed on a TPU.  With no chip, under
``JAX_PLATFORMS=cpu``, or in rehearsal it exits non-zero and that line
is never printed.
"""

import argparse
import json
import math
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
BUDGET_SECS = 1150          # the driver allows 1200, compilation included
NO_CHIP = 4                 # a child's exit code when JAX shows no TPU

# Mistral-7B as published (megatron_llm_tpu/models/mistral.py "7B"); the
# rehearsal keeps the architecture and shrinks everything.
FULL = dict(hidden=4096, heads=32, kv_heads=8, ffn=14336, vocab=32000,
            seq=4096)
TINY = dict(hidden=128, heads=4, kv_heads=2, ffn=352, vocab=512, seq=256)
PUBLISHED_LAYERS = 32
# Depths read off compiled.memory_analysis() of the real programs, compiled
# for a described v5e chip (16 GB HBM, 15.75 GiB usable) — compiler counts,
# not chip runs:
#  * train step with fp32 master + Adam moments: 2 layers (698M params)
#    take 9.11 GiB of state + 3.93 GiB of temporaries = 13.0 GiB; 3 layers
#    take 17.0 GiB.
#  * the four-chip pair: its one-chip side holds a global batch of TWO
#    sequences next to the whole optimizer state, which at 2 layers is
#    15.1 GiB (micro-batch 2) or 17.0 GiB (2 accumulated micro-batches);
#    1 layer (480M params) takes 10.0 GiB.  Both sides run that depth.
#  * engine decode/prefill: 16 layers of bf16 weights (7.0 GiB) + the
#    default 8-slot x 4096-token paged KV pool (2.0 GiB, not donated, so
#    held twice across a step) + 0.7 GiB of temporaries = 11.7 GiB.
TRAIN_LAYERS = 2
FOUR_CHIP_LAYERS = 1
SERVE_LAYERS = 16


def _model_flags(size, layers):
    return [
        "--model_name=mistral", f"--num_layers={layers}",
        f"--hidden_size={size['hidden']}",
        f"--num_attention_heads={size['heads']}",
        f"--num_attention_heads_kv={size['kv_heads']}",
        f"--ffn_hidden_size={size['ffn']}",
        f"--seq_length={size['seq']}",
        f"--max_position_embeddings={size['seq']}",
        "--bf16", "--micro_batch_size=1",
    ]


# ---------------------------------------------------------------------------
# children (these import JAX)
# ---------------------------------------------------------------------------

class _Watched:
    """A jitted function that remembers the abstract arguments of its
    first call, so that the compiled program's text can be asked for
    after the run (the entry points keep their jitted steps to
    themselves)."""

    def __init__(self, fn, on_first_call=None):
        self.fn, self.spec, self.on_first_call = fn, None, on_first_call

    def __call__(self, *args):
        if self.spec is None:
            import jax

            def abstract(x):
                if not hasattr(x, "shape"):
                    return x            # python scalars stay weak-typed
                sharding = (x.sharding if getattr(x, "committed", False)
                            else None)
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=sharding)

            if self.on_first_call is not None:
                self.on_first_call(*args)
            self.spec = jax.tree_util.tree_map(abstract, args)
        return self.fn(*args)

    def compiled_text(self) -> str:
        return self.fn.lower(*self.spec).compile().as_text()


class _CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, heard
    from jax.monitoring (a cache hit is a 'compile' of a few ms)."""

    def __init__(self):
        import jax

        self.secs, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self, on_chip):
        out = {"compiles": self.compiles, "cache_hits": self.hits,
               "cache_misses": self.misses}
        if on_chip:         # a time is a device-run number or nothing
            out["compile_secs"] = round(self.secs, 2)
        return out


def _start_child(args):
    """Common child start-up: the device as JAX reports it, the kernels
    in interpret mode for a rehearsal.  Exits when there is no chip."""
    sys.path.insert(0, ROOT)
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX reports {device}); --rehearse "
              f"walks the code on the CPU", file=sys.stderr)
        sys.exit(NO_CHIP)
    if args.rehearse:
        from megatron_llm_tpu.ops.pallas import (
            flash_attention, paged_attention, rmsnorm)

        for mod in (flash_attention, paged_attention, rmsnorm):
            mod._INTERPRET = True
    return device, dev.platform == "tpu"


def _size(args):
    """(widths, depth) of this run: a rehearsal walks the code at tiny
    widths and depth 2."""
    return (TINY, 2) if args.rehearse else (FULL, args.layers)


def _finish(record, checks):
    """Print the phase record; the exit code says whether it passed.
    A check that needs the chip is None (not run) in a rehearsal."""
    failed = sorted(k for k, v in checks.items() if v is False)
    record.update(checks=checks, failed=failed, passed=not failed)
    print(json.dumps(record), flush=True)
    return 0 if not failed else 1


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def _count_ops(hlo_text):
    """Instruction counts by opcode in an optimized HLO module's text:
    the Mosaic kernels (``tpu_custom_call``) and the collectives."""
    counts = {"tpu_custom_call":
              hlo_text.count('custom_call_target="tpu_custom_call"')}
    for op in _COLLECTIVES:
        counts[op] = sum(hlo_text.count(f" {op}{suffix}(")
                         for suffix in ("", "-start"))
    return counts


def phase_train(args):
    device, on_chip = _start_child(args)
    import jax
    import numpy as np

    import finetune
    from megatron_llm_tpu import training

    size, layers = _size(args)
    meter = _CompileMeter()
    param_bytes = {}

    def on_first_step(params, *_):
        # where the parameters really live: bytes on each device
        for leaf in jax.tree_util.tree_leaves(params):
            for shard in leaf.addressable_shards:
                param_bytes[shard.device.id] = (
                    param_bytes.get(shard.device.id, 0) + shard.data.nbytes)

    steps = []
    build = training.build_train_step

    def watched_build(*a, **kw):
        steps.append(_Watched(build(*a, **kw), on_first_step))
        return steps[-1]

    training.build_train_step = watched_build

    with tempfile.TemporaryDirectory() as tmp:
        sys.argv = ["finetune.py"] + _model_flags(size, layers) + [
            f"--vocab_size={size['vocab']}",
            f"--global_batch_size={args.global_batch}",
            f"--tensor_model_parallel_size={args.tp}",
            f"--train_iters={args.train_iters}", "--lr=1e-4",
            "--recompute_granularity=selective", "--log_interval=1",
            f"--seed={args.seed}",
            f"--structured_log_dir={tmp}", f"--trace_dir={tmp}",
        ] + (["--sequence_parallel"] if args.tp > 1 else [])
        t0 = time.perf_counter()
        finetune.main()
        wall = time.perf_counter() - t0
        with open(os.path.join(tmp, "telemetry.jsonl")) as f:
            recs = [r for r in map(json.loads, f) if r.get("kind") == "log"]

    losses = [r["lm_loss"] for r in recs]
    grad_norms = [r["grad_norm"] for r in recs]
    recompiles = [r["recompiles"] for r in recs]
    step_secs = [r["step_time_secs"] for r in recs]
    ops = _count_ops(steps[0].compiled_text())
    params = jax.tree_util.tree_leaves(steps[0].spec[0])
    model_bytes = sum(p.size * p.dtype.itemsize for p in params)
    share = model_bytes / args.tp
    expected_first_loss = (math.log(size["vocab"])
                           + 0.5 * 0.02 ** 2 * size["hidden"])
    peaks = {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()} if on_chip else {}
    checks = {
        "ran_all_steps": len(recs) == args.train_iters,
        "finite": bool(np.all(np.isfinite(losses + grad_norms))),
        "grad_norm_nonzero": all(g > 0 for g in grad_norms),
        # random weights know nothing: the loss is ln(vocab) plus half
        # the variance of the logits, which the N(0, 0.02) head puts at
        # 0.02^2 x hidden over a unit-RMS final norm (11.19 at 7B width)
        "first_loss_as_random_weights_give":
            abs(losses[0] - expected_first_loss) < 0.2,
        # a compile while step 2 runs is tolerated, none after it
        "no_compile_after_step_2": len(set(recompiles[1:])) == 1,
        "mosaic_in_train_step":
            ops["tpu_custom_call"] > 0 if on_chip else None,
        # tp splits every matrix and dp replicates: each device holds
        # about total/tp (the replicated norm scales add a little)
        "params_sharded_over_tp": (
            len(param_bytes) == device["count"]
            and all(0.95 * share <= b <= 1.1 * share
                    for b in param_bytes.values())),
    }
    if args.tp > 1:
        checks["collectives_in_train_step"] = (
            ops["all-reduce"] > 0 and ops["all-gather"] > 0)
        if on_chip:
            checks["peak_memory_balanced"] = (
                max(peaks.values()) < 1.25 * min(peaks.values()))
    record = {
        "phase": args.label, "device": device, "rehearsal": args.rehearse,
        "model": dict(size, layers=layers,
                      published_layers=PUBLISHED_LAYERS,
                      cut="depth only, to what one 16 GB chip holds with "
                          "the fp32 master + Adam state (see the depths "
                          "at the top of chip_smoke.py)"),
        "mesh": {"tp": args.tp, "dp": device["count"] // args.tp,
                 "sequence_parallel": args.tp > 1},
        "global_batch": args.global_batch, "seed": args.seed,
        "params": sum(p.size for p in params), "param_bytes": model_bytes,
        "losses": losses, "grad_norms": grad_norms,
        "expected_first_loss": round(expected_first_loss, 3),
        "recompiles_by_step": recompiles,
        "compiled_ops": ops,
        "param_bytes_by_device": {str(k): v for k, v
                                  in sorted(param_bytes.items())},
        **meter.report(on_chip),
    }
    if on_chip:
        record.update(
            first_step_secs=round(step_secs[0], 2),
            steady_step_secs=round(float(np.median(step_secs[2:])), 4),
            wall_secs=round(wall, 1),
            peak_bytes_by_device=peaks)
    return _finish(record, checks)


def _put(port, path, body, timeout=600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="PUT")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=60) as r:
        return r.status, json.loads(r.read())


def phase_serve(args):
    device, on_chip = _start_child(args)
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import run_text_generation_server as srv
    from megatron_llm_tpu import tracing
    from megatron_llm_tpu.initialize import initialize_megatron
    from megatron_llm_tpu.telemetry import device_memory_stats

    size, layers = _size(args)
    meter = _CompileMeter()
    with tempfile.TemporaryDirectory() as tmp:
        argv = _model_flags(size, layers) + [
            "--global_batch_size=1", "--tokenizer_type=NullTokenizer",
            # NullTokenizer adds one id (eod): 31999 + 1 = the 32000 head
            f"--vocab_size={size['vocab'] - 1}",
            "--serve_engine", "--host=127.0.0.1", "--port=0",
            f"--seed={args.seed}", f"--trace_dir={tmp}",
        ]
        t0 = time.perf_counter()
        server = srv.build_server(
            initialize_megatron(extra_args_provider=srv.extra_args,
                                args_list=argv), argv)
        startup = time.perf_counter() - t0
        engine = server.generator.engine
        engine._decode_step = _Watched(engine._decode_step)
        engine._prefill_step = _Watched(engine._prefill_step)
        thread = threading.Thread(target=server.run,
                                  args=("127.0.0.1", 0), daemon=True)
        thread.start()
        for _ in range(600):
            if server.httpd is not None:
                break
            time.sleep(0.05)
        port = server.httpd.server_address[1]

        rng = np.random.RandomState(args.seed)
        chunk = engine.config.prefill_chunk

        def prompt(n):
            return " ".join(map(str, rng.randint(1, size["vocab"] - 1, n)))

        def ask(text, new):
            code, body = _put(port, "/api", {
                "prompts": [text], "tokens_to_generate": new,
                "temperature": 0.0})
            return {"status": code, "prompt_tokens": len(text.split()),
                    "asked": new, "tokens": body["tokens"][0]}

        short, long_ = prompt(9), prompt(3 * chunk + 11)
        answers = {"short": ask(short, 16), "long": ask(long_, 16)}
        pair = {}
        threads = [threading.Thread(
            target=lambda k=k, p=p: pair.update({k: ask(p, 24)}))
            for k, p in (("pair_a", prompt(40)), ("pair_b", prompt(90)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        answers.update(pair)
        cached_before = engine.prefill_tokens_cached
        answers["repeat"] = ask(long_, 16)
        prefix_hit_tokens = engine.prefill_tokens_cached - cached_before

        health = _get(port, "/health")
        metrics = _get(port, "/metrics")
        recompiles = tracing.get_tracing().recompile.recompiles
        _put(port, "/drain", {})
        thread.join(120)
        stats = engine.stats()
        ops = {"decode": _count_ops(engine._decode_step.compiled_text()),
               "prefill": _count_ops(engine._prefill_step.compiled_text())}

    checks = {
        "all_answered_200": (len(answers) == 5 and all(
            a["status"] == 200 for a in answers.values())),
        "token_counts": all(
            len(a["tokens"]) == a["prompt_tokens"] + a["asked"]
            for a in answers.values()),
        "long_prompt_spans_chunks": answers["long"]["prompt_tokens"] > chunk,
        "repeat_same_tokens":
            answers["repeat"]["tokens"] == answers["long"]["tokens"],
        "repeat_hit_prefix_cache": prefix_hit_tokens >= chunk,
        "kernels_pallas": (engine.paged_kernel == "pallas"
                           and engine.prefill_kernel == "pallas"),
        "no_nonfinite": stats["slots_evicted_nonfinite"] == 0,
        "no_recompile_after_warmup": recompiles == 0,
        "health_and_metrics": (health[0] == 200
                               and health[1]["status"] == "ok"
                               and metrics[0] == 200
                               and "engine" in metrics[1]),
        "clean_drain": (not thread.is_alive() and server.draining
                        and set(stats["finished"]) <= {"length", "stop"}),
        "mosaic_in_decode":
            ops["decode"]["tpu_custom_call"] > 0 if on_chip else None,
        "mosaic_in_prefill":
            ops["prefill"]["tpu_custom_call"] > 0 if on_chip else None,
    }
    record = {
        "phase": args.label, "device": device, "rehearsal": args.rehearse,
        "model": dict(size, layers=layers,
                      published_layers=PUBLISHED_LAYERS,
                      cut="depth only, to what one 16 GB chip holds with "
                          "bf16 weights and the default 8-slot paged KV "
                          "pool at 4096 tokens a slot"),
        "seed": args.seed,
        "requests": {k: {"prompt_tokens": a["prompt_tokens"],
                         "new_tokens": len(a["tokens"]) - a["prompt_tokens"],
                         "status": a["status"]}
                     for k, a in answers.items()},
        "paged_kernel": engine.paged_kernel,
        "prefill_kernel": engine.prefill_kernel,
        "prefix_hit_tokens": int(prefix_hit_tokens),
        "decode_steps": stats["decode_steps"],
        "prefill_chunks": stats["prefill_chunks"],
        "recompiles_after_warmup": recompiles,
        "compiled_ops": ops,
        **meter.report(on_chip),
    }
    if on_chip:
        record.update(
            startup_secs=round(startup, 1),
            decode_secs=stats["decode_secs"],
            prefill_secs=stats["prefill_secs"],
            # host clocks by phase of the loop (dispatch + fetch is
            # what the host waited, never device time)
            loop_phase_secs=stats["loop"]["phase_secs"],
            peak_bytes_in_use=device_memory_stats().get(
                "peak_bytes_in_use"))
    return _finish(record, checks)


# ---------------------------------------------------------------------------
# parent (no JAX)
# ---------------------------------------------------------------------------

def _child_env(rehearse, devices, host_chips):
    """What shows a child ``devices`` devices: that many virtual CPU
    devices in a rehearsal; on the chip, the whole host, or ONE chip of
    a four-chip host through the TPU runtime's own variables."""
    if rehearse:
        return {"JAX_PLATFORMS": "cpu", "XLA_FLAGS":
                f"--xla_force_host_platform_device_count={devices}"}
    if devices < host_chips:
        return {"TPU_VISIBLE_CHIPS": "0", "TPU_PROCESS_BOUNDS": "1,1,1",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1"}
    return {}


def _run_child(label, phase, devices, opts, args, deadline):
    """Run one phase to its end; returns its record (None if it printed
    none).  The child and anything it started are gone on return."""
    env = dict(os.environ,
               **_child_env(args.rehearse, devices, args.chips))
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--label", label, "--seed", str(args.seed)] + opts
    if args.rehearse:
        cmd.append("--rehearse")
    proc = subprocess.Popen(cmd, env=env, cwd=ROOT, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    timer = threading.Timer(max(deadline - time.time(), 1.0),
                            lambda: os.killpg(proc.pid, signal.SIGKILL))
    timer.start()
    record = None
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.startswith('{"phase"'):
                record = json.loads(line)
        rc = proc.wait()
    finally:
        timer.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    if rc != 0 and record is not None:
        record["passed"] = False
    print(f"chip_smoke: phase {label} exited {rc}", file=sys.stderr,
          flush=True)
    if rc == NO_CHIP:
        sys.exit(NO_CHIP)       # no chip: no result of any kind
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never passes")
    # a child's own options (the parent sets them)
    ap.add_argument("--phase", choices=("train", "serve"))
    ap.add_argument("--label")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--global_batch", type=int, default=1)
    ap.add_argument("--train_iters", type=int, default=5)
    args = ap.parse_args()
    if args.phase:
        return {"train": phase_train, "serve": phase_serve}[args.phase](args)

    if args.chips == 4:
        # tp2 x dp2 with sequence parallelism against the same global
        # batch on one chip (two accumulated micro-batches there)
        both = ["--layers", str(FOUR_CHIP_LAYERS), "--global_batch", "2",
                "--train_iters", "3"]
        plan = [("train_tp2_dp2", "train", 4, both + ["--tp", "2"]),
                ("train_one_chip", "train", 1, both)]
    else:
        plan = [("train", "train", 1, ["--layers", str(TRAIN_LAYERS)]),
                ("serve", "serve", 1, ["--layers", str(SERVE_LAYERS)])]

    deadline = time.time() + BUDGET_SECS
    records = [_run_child(*step, args, deadline) for step in plan]
    passed = all(r is not None and r["passed"] for r in records)
    if passed and args.chips == 4:
        # same seed, same global batch, same data: the two runs differ
        # only in reduction order and in bf16 rounding of sharded matmuls
        a, b = (r["losses"] for r in records)
        tolerance = 0.01
        worst = max(abs(x - y) for x, y in zip(a, b))
        passed = (worst <= tolerance
                  and records[1]["device"]["count"] == 1)
        print(json.dumps({"phase": "compare", "losses_tp2_dp2": a,
                          "losses_one_chip": b, "tolerance": tolerance,
                          "max_abs_diff": worst,
                          "one_chip_devices": records[1]["device"]["count"],
                          "passed": passed}), flush=True)
    device = next((r["device"] for r in records if r), None)
    ok = bool(passed and not args.rehearse and device
              and device["platform"] == "tpu"
              and device["count"] == args.chips)
    if not ok:
        # never the success line: no chip, a failed phase, or a rehearsal
        print(json.dumps({"ok": False, "rehearsal": args.rehearse,
                          "phases_passed": passed, "device": device}),
              flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
