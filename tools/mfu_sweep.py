"""On-chip MFU sweep harness (the tool behind docs/perf_tpu.md).

Usage: python tools/mfu_sweep.py <group>   (groups defined at the bottom)
Each trial builds a fresh llama-family model + fused-Adam train step,
runs 2 warmup + 5 timed iterations and prints ms/iter, tokens/s and MFU.
Timing syncs use a host-side scalar fetch: a real data round trip, so
the clock cannot stop before the last step has run.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax

from tools.bench_harness import (enable_compile_cache, make_cfg,
                                 build_concrete, make_batch)

from megatron_llm_tpu.telemetry import peak_flops_for_kind

enable_compile_cache()

def bench_cfg(label, mb=8, remat="selective", flash=True, fused_rms=True,
              L=16, h=1280, ffn=3584, heads=16, seq=2048, iters=5, bq=None,
              bk=None, experts=0, top_k=2, fused_bwd=None, vocab=32000,
              fused_ce=False, opt_state_dtype="fp32"):
    import megatron_llm_tpu.ops.pallas.flash_attention as fa
    orig_bq, orig_bk = fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K
    orig_fused = fa.FUSED_BACKWARD
    if bq: fa.DEFAULT_BLOCK_Q = bq
    if bk: fa.DEFAULT_BLOCK_K = bk
    if fused_bwd is not None: fa.FUSED_BACKWARD = fused_bwd
    try:
        # model/optimizer init INSIDE the trial guard: the memory-edge
        # trials (bigvocab) can OOM at init, which must fail that one
        # trial, not abort the sweep
        cfg = make_cfg(L=L, h=h, heads=heads, ffn=ffn, seq=seq,
                       vocab=vocab, remat=remat, flash=flash,
                       fused_rms=fused_rms, experts=experts, top_k=top_k,
                       fused_ce=fused_ce)
        model, params, opt, opt_state, step = build_concrete(
            cfg, mb, opt_state_dtype=opt_state_dtype)
        n = model.num_params(params)
        batch = make_batch(mb, seq, vocab)
        key = jax.random.PRNGKey(1)
        for _ in range(2):
            params, opt_state, m = step(params, opt_state, batch, key, 1e-4, 0.0)
            float(m["lm loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, m = step(params, opt_state, batch, key, 1e-4, 0.0)
        float(m["lm loss"])
        dt = (time.perf_counter() - t0) / iters
        tps = mb * seq / dt
        mfu = tps * model.flops_per_token() / peak_flops_for_kind(
            jax.devices()[0].device_kind)
        print(f"{label:44s} n={n/1e6:6.1f}M dt={dt*1000:8.1f}ms tps={tps:9.1f} mfu={mfu:.3f}", flush=True)
    except Exception as e:
        print(f"{label:44s} FAILED: {type(e).__name__}: {str(e)[:120]}", flush=True)
    fa.DEFAULT_BLOCK_Q = orig_bq
    fa.DEFAULT_BLOCK_K = orig_bk
    fa.FUSED_BACKWARD = orig_fused

GROUPS = {
    "baseline": [
        dict(label="flash defaults mb4", mb=4),
        dict(label="flash defaults mb8", mb=8),
        dict(label="xla attention mb4", mb=4, flash=False),
    ],
    "blocks": [
        dict(label="flash bq128 bk128", bq=128, bk=128),
        dict(label="flash bq256 bk256", bq=256, bk=256),
        dict(label="flash bq512 bk512", bq=512, bk=512),
        dict(label="flash bq1024 bk1024", bq=1024, bk=1024),
    ],
    "mb": [
        dict(label="flash mb2", mb=2),
        dict(label="flash mb4", mb=4),
        dict(label="flash mb8", mb=8),
        dict(label="flash mb16", mb=16),
    ],
    "remat": [
        dict(label="selective", remat="selective", mb=4),
        dict(label="full", remat="full", mb=4),
        dict(label="none", remat="none", mb=4),
    ],
    "long": [
        dict(label="seq4096 mb4 flash", seq=4096, mb=4),
        dict(label="seq8192 mb2 flash", seq=8192, mb=2),
        dict(label="seq4096 mb4 xla", seq=4096, mb=4, flash=False),
    ],
}
GROUPS["shape"] = [
    dict(label="h1280 nh16 d80", mb=4),
    dict(label="h1280 nh10 d128", mb=4, heads=10),
    dict(label="h2048 nh16 d128 L10 (bench)", mb=4, h=2048, heads=16, ffn=5632, L=10),
    dict(label="h2048 nh16 d128 L10 mb2", mb=2, h=2048, heads=16, ffn=5632, L=10),
]
GROUPS["shape2"] = [
    dict(label="h2048 L10 mb8", mb=8, h=2048, heads=16, ffn=5632, L=10),
    dict(label="h2048 L12 mb4", mb=4, h=2048, heads=16, ffn=5632, L=12),
    dict(label="h2560 nh20 L8 mb4", mb=4, h=2560, heads=20, ffn=6912, L=8),
]
GROUPS["tune650"] = [
    dict(label="650M bq1024 bk1024 (bench)", mb=4, h=2048, heads=16, ffn=5632, L=10),
    dict(label="650M bq512 bk1024", mb=4, h=2048, heads=16, ffn=5632, L=10, bq=512, bk=1024),
    dict(label="650M bq1024 bk512", mb=4, h=2048, heads=16, ffn=5632, L=10, bq=1024, bk=512),
    dict(label="650M remat full", mb=4, h=2048, heads=16, ffn=5632, L=10, remat="full"),
    dict(label="650M mb6", mb=6, h=2048, heads=16, ffn=5632, L=10),
]
GROUPS["moe"] = [
    # MoE on one chip: all experts local (ep needs a mesh); measures the
    # dispatch/combine einsum overhead vs the dense MLP at matched
    # active-FLOPs (dense ffn == top_k * moe ffn per token)
    dict(label="dense h2048 L10 ffn5632 (bench)",
         mb=4, h=2048, heads=16, ffn=5632, L=10),
    dict(label="moe E4 top2 ffn2816 (matched active)",
         mb=4, h=2048, heads=16, ffn=2816, L=10, experts=4),
    dict(label="moe E8 top2 ffn2816",
         mb=4, h=2048, heads=16, ffn=2816, L=10, experts=8),
]
# round-4: the fused single-pass flash backward (the round-3 "known
# headroom") A/B'd at the bench shape and at matched-baseline seq 4096 —
# VERDICT r3 #2 wants MFU >= 0.47 at seq 4096
GROUPS["fusedbwd"] = [
    dict(label="650M seq2048 two-kernel bwd", mb=4, h=2048, heads=16,
         ffn=5632, L=10, fused_bwd=False),
    dict(label="650M seq2048 fused bwd", mb=4, h=2048, heads=16,
         ffn=5632, L=10, fused_bwd=True),
    dict(label="650M seq4096 two-kernel bwd", mb=2, h=2048, heads=16,
         ffn=5632, L=10, seq=4096, fused_bwd=False),
    dict(label="650M seq4096 fused bwd", mb=2, h=2048, heads=16,
         ffn=5632, L=10, seq=4096, fused_bwd=True),
    dict(label="650M seq8192 two-kernel bwd", mb=1, h=2048, heads=16,
         ffn=5632, L=10, seq=8192, fused_bwd=False),
    dict(label="650M seq8192 fused bwd", mb=1, h=2048, heads=16,
         ffn=5632, L=10, seq=8192, fused_bwd=True),
]
GROUPS["seq4096"] = [
    dict(label="650M seq4096 mb1", mb=1, h=2048, heads=16, ffn=5632,
         L=10, seq=4096),
    dict(label="650M seq4096 mb2", mb=2, h=2048, heads=16, ffn=5632,
         L=10, seq=4096),
    dict(label="650M seq4096 mb4", mb=4, h=2048, heads=16, ffn=5632,
         L=10, seq=4096),
    dict(label="650M seq4096 mb2 bq2048", mb=2, h=2048, heads=16,
         ffn=5632, L=10, seq=4096, bq=2048, bk=1024),
    dict(label="650M seq4096 mb2 bk2048", mb=2, h=2048, heads=16,
         ffn=5632, L=10, seq=4096, bq=1024, bk=2048),
    dict(label="650M seq4096 mb2 full-remat", mb=2, h=2048, heads=16,
         ffn=5632, L=10, seq=4096, remat="full"),
]
# fused chunked linear+CE flip point (VERDICT r3 #8): at 32k vocab it
# measured a tie (docs/perf_tpu.md "tried and rejected"); the claim is
# the trade flips at 128k vocab where the [tokens, vocab] fp32 logits
# block is 4x bigger.  Smaller L keeps the 128k-vocab embedding+head
# (h2048: 2 x 0.5 GB bf16) inside 16 GB next to the Adam state.
GROUPS["bigvocab"] = [
    dict(label="v32k  unfused (bench cfg)", mb=4, h=2048, heads=16,
         ffn=5632, L=10),
    dict(label="v32k  fused-CE", mb=4, h=2048, heads=16, ffn=5632, L=10,
         fused_ce=True),
    dict(label="v128k unfused", mb=4, h=2048, heads=16, ffn=5632, L=8,
         vocab=131072),
    dict(label="v128k fused-CE", mb=4, h=2048, heads=16, ffn=5632, L=8,
         vocab=131072, fused_ce=True),
    dict(label="v256k unfused", mb=2, h=2048, heads=16, ffn=5632, L=6,
         vocab=262144),
    dict(label="v256k fused-CE", mb=2, h=2048, heads=16, ffn=5632, L=6,
         vocab=262144, fused_ce=True),
]
# bf16 optimizer-state A/B (optimizer_state_dtype): the Adam moments are
# pure HBM traffic in the step — storing them bf16 halves those
# bytes.  Same shape as the bench config.
GROUPS["optstate"] = [
    dict(label="650M fp32 moments (bench)", mb=4, h=2048, heads=16,
         ffn=5632, L=10),
    dict(label="650M bf16 moments", mb=4, h=2048, heads=16, ffn=5632,
         L=10, opt_state_dtype="bf16"),
    dict(label="650M seq4096 bf16 moments", mb=2, h=2048, heads=16,
         ffn=5632, L=10, seq=4096, opt_state_dtype="bf16"),
]
GROUPS["all"] = GROUPS["baseline"] + GROUPS["blocks"]

if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which not in GROUPS:
        print(f"unknown group {which!r}; available: {', '.join(GROUPS)}")
        sys.exit(1)
    for trial in GROUPS[which]:
        bench_cfg(**trial)
