#!/usr/bin/env python
"""Benchmark: training throughput (tokens/sec/chip) + MFU on one chip.

Prints ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Baseline: the reference's derived Llama-2-7B finetune throughput is
~3.5k tokens/sec per A100-80GB (BASELINE.md).  A single TPU chip can't
hold 7B training state, so the bench trains a mid-size Llama-family
model on one chip and reports MFU alongside raw tokens/sec;
``vs_baseline`` compares achieved MFU against the reference's implied
A100 MFU on its 7B recipe (~3.5k tok/s x 6x7e9 FLOP/tok / 312 TFLOPs
= 47%), i.e. vs_baseline > 1 means better hardware utilization than the
reference's own headline recipe.

Contract (the driver runs this unattended):
 * it measures on the TPU it finds, or exits non-zero: no CPU stand-in,
   no replay of an earlier number, no kernel quietly swapped for XLA;
 * the parent process imports NO jax (the chip belongs to one process);
   it runs the measurement in one child under a hard deadline and
   streams the child's stderr progress;
 * the child uses the persistent compilation cache
   (``initialize.enable_compile_cache``) so repeat runs skip compilation;
 * staged progress is printed to stderr with elapsed timestamps.
"""

import json
import os
import subprocess
import sys
import time

T0 = time.time()


def log(msg):
    print(f"[bench +{time.time() - T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Child: the actual measurement (runs with jax, under parent's deadline)
# --------------------------------------------------------------------------

# the per-chip bf16 peak table and the >0.95 MFU fabrication guard live in
# megatron_llm_tpu/telemetry.py (one source of truth with the runtime
# throughput stream); imported inside child_main only — the parent must
# stay jax-free
A100_REFERENCE_MFU = 0.47  # BASELINE.md derivation


def child_main():
    log("child: importing jax")
    import jax  # noqa: E402

    from megatron_llm_tpu.initialize import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    log("child: initializing backend (first device query)")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"child: no TPU (default backend {dev.platform!r}): this "
            f"benchmark measures on the chip or not at all")
        sys.exit(2)
    from megatron_llm_tpu.telemetry import (MFU_SANITY_LIMIT,
                                            peak_flops_for_kind)
    peak = peak_flops_for_kind(dev.device_kind)
    log(f"child: backend={jax.default_backend()} device={dev.device_kind}")

    from megatron_llm_tpu.timers import Timers
    timers = Timers(log_level=2)

    # span tracing + goodput + recompile accounting (tracing.py): the
    # bench classifies its own wall-clock (compile/warmup vs measured
    # steps) and reports goodput_pct / recompiles / straggler_events in
    # the BENCH artifact — a recompile during the measured loop is a
    # perf bug the artifact must confess to
    from megatron_llm_tpu import tracing as trace_mod
    tracer = trace_mod.SpanTracer(capacity=20000)
    detector = trace_mod.RecompileDetector(tracer=tracer)
    bundle = trace_mod.Tracing(
        tracer=tracer, recompile=detector,
        straggler=trace_mod.StragglerDetector(
            tracer=tracer, printer=lambda s: log(f"child: {s}")))
    trace_mod.install_tracing(bundle)

    from megatron_llm_tpu.config import ParallelConfig, TrainConfig
    from megatron_llm_tpu.models.llama import LlamaModel, llama_config
    from megatron_llm_tpu.optimizer import MegatronOptimizer
    from megatron_llm_tpu.training import build_train_step

    # ~650M llama, MXU-aligned head_dim=128 (docs/perf_tpu.md's shape
    # sweep: head_dim 80 wastes 3/8 of the 128-wide MXU lanes).  The
    # primary is seq 4096, the reference recipe's own sequence length;
    # seq 2048 mb 4 is the secondary block below.  mb2 at seq 4096: mb4
    # overflows 16 GB with the 650M Adam state.  The Pallas kernels run
    # as configured (flash attention, fused RMSNorm): one that fails to
    # lower fails the benchmark.
    sec_seq, sec_mb = 2048, 4
    cfg = llama_config(
        "tiny",
        num_layers=10, hidden_size=2048, num_attention_heads=16,
        ffn_hidden_size=5632, padded_vocab_size=32000,
        seq_length=4096, max_position_embeddings=4096,
        params_dtype="bf16", compute_dtype="bf16",
        recompute_granularity="selective",
    )
    micro_batch, num_micro = 2, 1
    model_name = "llama-650M"
    seq = cfg.seq_length

    log(f"child: building {model_name} (seq={seq}, mb={micro_batch})")
    timers("model-build", log_level=1).start()
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = model.num_params(params)
    timers("model-build").stop()
    log(f"child: {n_params/1e6:.1f}M params initialized")

    tc = TrainConfig(
        micro_batch_size=micro_batch, global_batch_size=micro_batch * num_micro,
        train_iters=0, lr=1e-4, optimizer="adam", bf16=True, clip_grad=1.0,
    )
    pc = ParallelConfig()
    opt = MegatronOptimizer(tc, params_dtype=jnp.bfloat16)
    opt_state = opt.init(params)
    step = build_train_step(model, opt, pc, num_micro)

    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(1)

    def timed_run(step, params, opt_state, batch, *, max_iters, budget_s,
                  label):
        """2 warmup steps + adaptive timed loop; returns
        (dt, iters, loss, params, opt_state) — the returned state handles
        are the *live* post-step buffers (the inputs are donated away on
        the first call), so a follow-up measurement can reuse them.

        Every sync is a host-side scalar fetch: ``float()`` is a real
        data round trip, so the clock cannot stop before the step has
        run.  One shared helper so the sync protocol cannot drift
        between measurements."""
        tc0 = time.time()
        detector.pause()        # warmup compiles are expected, not recompiles
        timers(f"{label}-compile-warmup", log_level=1).start()
        with tracer.span(f"{label}_warmup", "compile"):
            for _ in range(2):
                params, opt_state, m = step(params, opt_state, batch, key,
                                            1e-4, 0.0)
                float(m["lm loss"])
        timers(f"{label}-compile-warmup").stop()
        detector.resume()
        detector.mark_steady()  # any compile in the measured loop is a bug
        log(f"child: {label}: compile+warmup done in "
            f"{time.time() - tc0:.1f}s")
        iters = 0
        timers(f"{label}-measure", log_level=1).start()
        t0 = time.perf_counter()
        with tracer.span(f"{label}_measure", "step"):
            while iters < max_iters:
                params, opt_state, m = step(params, opt_state, batch, key,
                                            1e-4, 0.0)
                iters += 1
                if iters % 5 == 0 or iters == max_iters:
                    float(m["lm loss"])      # true sync (see docstring)
                    if time.perf_counter() - t0 > budget_s:
                        break
            loss = float(m["lm loss"])
        timers(f"{label}-measure").stop()
        dt = (time.perf_counter() - t0) / iters
        log(f"child: {label}: timed {iters} iters, {dt*1000:.1f} ms/iter")
        return dt, iters, loss, params, opt_state

    toks = jnp.asarray(rng.randint(0, cfg.padded_vocab_size,
                                   (num_micro, micro_batch, seq)))
    batch = {
        "tokens": toks,
        "labels": jnp.roll(toks, -1, axis=-1),
        "loss_mask": jnp.ones_like(toks, jnp.float32),
    }
    log("child: compiling train step (first call)")
    dt, iters, loss, params, opt_state = timed_run(
        step, params, opt_state, batch,
        max_iters=30, budget_s=20.0, label="primary")
    # per-phase report via the same Timers subsystem the train loop logs
    # with (megatron_llm_tpu/timers.py)
    timers.log(printer=lambda s: log(f"child: {s}"))

    tokens_per_iter = micro_batch * num_micro * seq
    tps = tokens_per_iter / dt
    flops_tok = model.flops_per_token()
    mfu = tps * flops_tok / peak
    if mfu > MFU_SANITY_LIMIT:
        # physically impossible: the timing loop failed to sync with the
        # device.  Refuse to emit a garbage number.
        log(f"child: MEASUREMENT_INVALID mfu={mfu:.2f} > "
            f"{MFU_SANITY_LIMIT} (dt={dt*1000:.2f} ms/iter cannot be real)")
        sys.exit(3)

    rec = {
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / A100_REFERENCE_MFU, 4),
        "mfu": round(mfu, 4),
        "model": model_name,
        "n_params": int(n_params),
        "seq_length": seq,
        "micro_batch": micro_batch,
        "device": dev.device_kind,
        "backend": jax.default_backend(),
        "ms_per_iter": round(dt * 1000, 2),
        "iters": iters,
        "loss": loss,
        "seq2048": None,
    }
    # fault-tolerance counters (resilience.py): zeros on a clean bench,
    # nonzero when a run rewound, retried a save, or tripped the watchdog
    from megatron_llm_tpu.resilience import recovery_counters

    rec["recovery"] = recovery_counters()
    # goodput attribution (tracing.py): measured-step share of the
    # child's wall-clock, plus steady-state recompile count (anything
    # nonzero means the measured loop retraced — the number above it is
    # polluted) and straggler events (always 0 single-host)
    g = tracer.goodput.summary()
    rec["goodput_pct"] = round(g["goodput_pct"], 2)
    rec["compile_secs"] = round(g["compile_secs"], 2)
    rec["recompiles"] = int(detector.recompiles)
    rec["straggler_events"] = int(bundle.straggler.total)
    # emit the PRIMARY result immediately — if a later block runs into
    # the parent deadline, this line is already on stdout
    rec["layer_stats_overhead_pct"] = None
    print(json.dumps(rec), flush=True)

    # model-health observatory overhead (health.py): the same step with
    # per-layer stats enabled, timed under the identical sync protocol.
    # The stats are computed every iteration here (the host fetch at
    # --log_layer_stats_interval is off the measured path), so this is an
    # upper bound on the interval-10 cost.  A regression >= 3% ms/iter is
    # a hard failure — the observatory must never silently tax the hot
    # path.
    log("child: layer-stats overhead measurement")
    step_ls = build_train_step(model, opt, pc, num_micro,
                               log_layer_stats=True)
    dt_ls, _, _, params, opt_state = timed_run(
        step_ls, params, opt_state, batch,
        max_iters=30, budget_s=10.0, label="layer-stats")
    overhead_pct = (dt_ls - dt) / dt * 100.0
    rec["layer_stats_overhead_pct"] = round(overhead_pct, 2)
    log(f"child: layer-stats overhead {overhead_pct:+.2f}% ms/iter "
        f"({dt_ls*1000:.1f} vs {dt*1000:.1f})")
    print(json.dumps(rec), flush=True)
    if overhead_pct >= 3.0:
        log(f"child: LAYER_STATS_OVERHEAD_REGRESSION {overhead_pct:.2f}% "
            f">= 3% — fix health.py before shipping (the BENCH record "
            f"above already carries the number)")
        sys.exit(4)

    # secondary measurement at seq 2048 mb 4 (the earlier rounds' primary
    # shape, kept so the rows stay comparable), only if the primary
    # finished early enough
    cutoff = float(os.environ.get("BENCH_SECONDARY_CUTOFF_S", "300"))
    if time.time() - T0 < cutoff \
            and os.environ.get("BENCH_NO_SECONDARY") != "1":
        # free the primary's HBM (donated chains end at these handles)
        # before building a second full model + Adam state on a 16-GB chip
        del params, opt_state, batch, toks
        log(f"child: secondary seq-{sec_seq} measurement")
        cfg2 = cfg.replace(seq_length=sec_seq,
                           max_position_embeddings=sec_seq)
        model2 = LlamaModel(cfg2)
        params2 = model2.init(jax.random.PRNGKey(0))
        opt2 = MegatronOptimizer(tc, params_dtype=jnp.bfloat16)
        os2 = opt2.init(params2)
        step2 = build_train_step(model2, opt2, pc, 1)
        t2 = jnp.asarray(rng.randint(0, cfg.padded_vocab_size,
                                     (1, sec_mb, sec_seq)))
        b2 = {"tokens": t2, "labels": jnp.roll(t2, -1, axis=-1),
              "loss_mask": jnp.ones_like(t2, jnp.float32)}
        dt2, it2, _, _, _ = timed_run(step2, params2, os2, b2,
                                      max_iters=10, budget_s=10.0,
                                      label="seq2048")
        tps2 = sec_mb * sec_seq / dt2
        mfu2 = tps2 * model2.flops_per_token() / peak
        if mfu2 > MFU_SANITY_LIMIT:
            log(f"child: seq2048 MEASUREMENT_INVALID mfu={mfu2:.2f} > "
                f"{MFU_SANITY_LIMIT}")
            sys.exit(3)
        rec["seq2048"] = {
            "value": round(tps2, 1), "mfu": round(mfu2, 4),
            "vs_baseline": round(mfu2 / A100_REFERENCE_MFU, 4),
            "micro_batch": sec_mb, "seq_length": sec_seq,
            "ms_per_iter": round(dt2 * 1000, 2),
            "iters": it2,
        }
        log(f"child: seq2048 {tps2:.0f} tok/s mfu={mfu2:.3f}")
        print(json.dumps(rec), flush=True)


# --------------------------------------------------------------------------
# Parent: one child under a deadline (no jax imported here)
# --------------------------------------------------------------------------

def main():
    """Run the measurement child and forward its last JSON line.  Exit
    code 0 only when the child measured on a TPU and exited cleanly."""
    # covers a COLD compile of every train step; a warm run takes about
    # two minutes.  Env-overridable for manual debugging.
    deadline_s = float(os.environ.get("BENCH_DEADLINE_S", "720"))
    log(f"parent: launching measurement child (deadline {deadline_s:.0f}s)")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=dict(os.environ, _BENCH_CHILD="1"),
        stdout=subprocess.PIPE, text=True)      # stderr streams through
    try:
        out, _ = proc.communicate(timeout=deadline_s)
    except subprocess.TimeoutExpired:
        log("parent: deadline reached, terminating child")
        proc.terminate()
        try:
            out, _ = proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
        return 124
    # last matching line wins: the child emits the primary result first
    # and re-emits an enriched record as each secondary block lands
    lines = [ln.strip() for ln in out.splitlines()
             if ln.strip().startswith("{") and '"metric"' in ln]
    if lines:
        print(lines[-1], flush=True)    # measured on the chip, even if a
    if proc.returncode != 0 or not lines:   # later block then failed
        log(f"parent: child exited rc={proc.returncode} with "
            f"{len(lines)} result line(s)")
        return proc.returncode or 1
    log("parent: done")
    return 0


if __name__ == "__main__":
    if os.environ.get("_BENCH_CHILD") == "1":
        child_main()
    else:
        sys.exit(main())
