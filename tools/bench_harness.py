"""Shared harness for the perf tools (mfu_sweep, profile_step).

One place for the model/optimizer/train-step/batch construction and the
persistent-compile-cache setup, so the batch contract ([num_micro, mb,
seq] tokens/labels/loss_mask) and TrainConfig defaults cannot drift
between tools.  bench.py deliberately does NOT import this: the driver
artifact must stay self-contained (it is run by an external harness
under its own deadline).
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# the one persistent-compile-cache helper (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache), re-exported for the tools that import this harness
from megatron_llm_tpu.initialize import enable_compile_cache  # noqa: E402,F401


# the on-chip bench shape (docs/perf_tpu.md): ~650M llama, MXU-aligned
# head_dim 128 — ONE definition shared by bench-shape presets in
# profile_step / decode_bench (mfu_sweep's GROUPS spell shapes out per
# trial because shapes ARE its sweep axes)
BENCH_SHAPE = dict(L=10, h=2048, heads=16, ffn=5632)


def make_cfg(*, L=16, h=1280, heads=16, ffn=3584, seq=2048, vocab=32000,
             remat="selective", flash=True, fused_rms=True, experts=0,
             top_k=2, fused_ce=False):
    """The llama-family config every perf tool measures."""
    from megatron_llm_tpu.models.llama import llama_config
    return llama_config(
        "tiny", num_layers=L, hidden_size=h, num_attention_heads=heads,
        ffn_hidden_size=ffn, padded_vocab_size=vocab, seq_length=seq,
        max_position_embeddings=seq, params_dtype="bf16",
        compute_dtype="bf16", recompute_granularity=remat,
        use_flash_attn=flash, use_fused_rmsnorm=fused_rms,
        num_experts=experts, moe_top_k=top_k,
        fused_lm_cross_entropy=fused_ce)


def build_concrete(cfg, mb, num_micro=1, opt_state_dtype="fp32"):
    """Initialized (model, params, opt, opt_state, step) for one config."""
    import jax
    import jax.numpy as jnp
    from megatron_llm_tpu.config import ParallelConfig, TrainConfig
    from megatron_llm_tpu.models.llama import LlamaModel
    from megatron_llm_tpu.optimizer import MegatronOptimizer
    from megatron_llm_tpu.training import build_train_step
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tc = TrainConfig(micro_batch_size=mb,
                     global_batch_size=mb * num_micro, train_iters=0,
                     lr=1e-4, optimizer="adam", bf16=True, clip_grad=1.0,
                     optimizer_state_dtype=opt_state_dtype)
    opt = MegatronOptimizer(tc, params_dtype=jnp.bfloat16)
    opt_state = opt.init(params)
    step = build_train_step(model, opt, ParallelConfig(), num_micro)
    return model, params, opt, opt_state, step


def make_batch(mb, seq, vocab, num_micro=1, np_seed=0):
    """Synthetic [num_micro, mb, seq] batch in the train-step layout."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(np_seed)
    toks = jnp.asarray(rng.randint(0, vocab, (num_micro, mb, seq)))
    return {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
            "loss_mask": jnp.ones_like(toks, jnp.float32)}
