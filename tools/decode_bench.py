"""Decode (serving) throughput benchmark.

Training MFU is covered by bench.py / mfu_sweep.py; this measures the
generation stack (tools/run_text_generation_server.py's engine):
prefill throughput and steady-state decode tokens/s on the same ~650M
bench shape, greedy, jitted while-loop decode with the KV cache.

The decode rate is isolated by differencing two runs (gen N and gen 2N
tokens from the same prompts): decode_tps = b*N / (t_2N - t_N) — the
shared prefill and fixed overheads cancel, so neither needs to be
timed separately.

    python tools/decode_bench.py            # 650M, TPU shape
    python tools/decode_bench.py --preset tiny   # CPU / CI

Usage mirrors mfu_sweep: one line per trial.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from tools.bench_harness import BENCH_SHAPE, enable_compile_cache, make_cfg

import jax
import jax.numpy as jnp
import numpy as np

PRESETS = {
    "bench": dict(**BENCH_SHAPE, vocab=32000,
                  prompt=128, gen=256, batches=(1, 8)),
    "tiny": dict(L=2, h=128, heads=4, ffn=352, vocab=512,
                 prompt=16, gen=8, batches=(2,)),
}


def run_trial(model, params, b, prompt, gen, vocab, kv_int8=False):
    from megatron_llm_tpu.text_generation.generation import generate_tokens
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(1, vocab, (b, prompt)))
    lens = jnp.full((b,), prompt, jnp.int32)
    key = jax.random.PRNGKey(0)

    # both runs use the SAME cache allocation (prompt + 2*gen): decode
    # masks the unused tail, so per-step cost is identical between the
    # gen-N and gen-2N runs and the differencing below is unbiased
    cache = prompt + 2 * gen

    def timed(n_new):
        # compile (first call per n_new) then measure
        out = generate_tokens(model, params, toks, lens, key,
                              max_new_tokens=n_new, min_prompt_len=prompt,
                              greedy=True, cache_len=cache,
                              int8_kv_cache=kv_int8)
        float(out[1].sum())  # host sync: a real data round trip
        t0 = time.perf_counter()
        out = generate_tokens(model, params, toks, lens, key,
                              max_new_tokens=n_new, min_prompt_len=prompt,
                              greedy=True, cache_len=cache,
                              int8_kv_cache=kv_int8)
        float(out[1].sum())
        return time.perf_counter() - t0

    t1 = timed(gen)
    t2 = timed(2 * gen)
    e2e_tps = b * 2 * gen / t2
    tag = " kv-int8" if kv_int8 else ""
    if t2 - t1 < 0.05 * t2:
        # the N extra decode steps are inside run-to-run jitter: a
        # differenced rate would be noise presented as signal
        print(f"b={b:3d} prompt={prompt} gen={2*gen}{tag}: decode   INVALID "
              f"(t2-t1 jitter) | e2e {e2e_tps:9.1f} tok/s "
              f"(t={t2*1000:.0f} ms)", flush=True)
        return
    decode_tps = b * gen / (t2 - t1)
    print(f"b={b:3d} prompt={prompt} gen={2*gen}{tag}: "
          f"decode {decode_tps:9.1f} tok/s | e2e {e2e_tps:9.1f} tok/s "
          f"(t={t2*1000:.0f} ms)", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--preset", choices=sorted(PRESETS), default="bench")
    args = ap.parse_args()
    enable_compile_cache()

    p = PRESETS[args.preset]
    on_tpu = jax.default_backend() == "tpu"
    seq_budget = p["prompt"] + 2 * p["gen"]
    cfg = make_cfg(L=p["L"], h=p["h"], heads=p["heads"], ffn=p["ffn"],
                   vocab=p["vocab"], seq=max(seq_budget, 128),
                   flash=False,  # decode is seq-1 steps: flash is a
                   fused_rms=on_tpu)  # prefill-only win, keep it simple
    from megatron_llm_tpu.models.llama import LlamaModel
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = model.num_params(params)
    print(f"decode_bench: {n/1e6:.0f}M params, backend="
          f"{jax.default_backend()}", flush=True)
    for b in p["batches"]:
        run_trial(model, params, b, p["prompt"], p["gen"], p["vocab"])
    # weight-only int8 A/B: decode re-reads every dense weight per
    # token, so halving those bytes targets the decode bandwidth bound
    from megatron_llm_tpu.quantization import quantize_linear_weights_int8
    qparams = quantize_linear_weights_int8(params)
    print("decode_bench: int8 weight-only quantized kernels", flush=True)
    for b in p["batches"]:
        run_trial(model, qparams, b, p["prompt"], p["gen"], p["vocab"])
    # int8 KV cache on top of int8 weights: fully int8 decode bytes
    print("decode_bench: + int8 KV cache", flush=True)
    for b in p["batches"]:
        run_trial(model, qparams, b, p["prompt"], p["gen"], p["vocab"],
                  kv_int8=True)
    # speculative prompt-lookup A/B on a repetitive prompt (the
    # favorable case: summarization/code-edit-like repetition) —
    # exactness is covered by tests/test_serving_engine.py's greedy
    # parity test, this measures the accepted-draft speedup through the
    # engine's fixed-shape K+1 verify step
    run_spec_trial(model, params, p["prompt"], p["gen"], p["vocab"])


def run_spec_trial(model, params, prompt, gen, vocab, draft_k=4):
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine
    from megatron_llm_tpu.serving.request import SamplingParams
    rng = np.random.RandomState(1)
    pattern = rng.randint(1, vocab, max(prompt // 4, 2))
    toks = [int(t) for t in np.tile(pattern, prompt // len(pattern) + 1)
            [:prompt]]
    sp = SamplingParams(max_new_tokens=2 * gen, temperature=0.0)

    def timed(speculative):
        eng = InferenceEngine(model, params, EngineConfig(
            num_slots=1, block_size=16,
            prefill_chunk=max(prompt, 16),
            max_model_len=prompt + 2 * gen + draft_k,
            default_deadline_secs=0.0,
            speculative=speculative, draft_k=draft_k))
        eng.warmup()
        eng.start()
        try:
            eng.submit(toks, sp).result(timeout=600)  # warm run
            t0 = time.perf_counter()
            r = eng.submit(toks, sp).result(timeout=600)
            dt = time.perf_counter() - t0
            return dt, len(r.out_tokens), eng.stats()
        finally:
            eng.stop()

    t_van, n_van, _ = timed(False)
    t_spec, n_spec, stats = timed(True)
    drafted = stats.get("drafted_tokens") or 0
    accepted = stats.get("accepted_tokens") or 0
    rate = f"{accepted / drafted:.2f}" if drafted else "-"
    print(f"b=  1 prompt={prompt} gen={2*gen} (repetitive): "
          f"greedy {n_van/t_van:9.1f} tok/s | speculative[K+1={draft_k+1}] "
          f"{n_spec/t_spec:9.1f} tok/s ({t_van/t_spec:.2f}x, "
          f"accept {rate})", flush=True)


if __name__ == "__main__":
    main()
