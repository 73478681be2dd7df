"""GPT-style decoder-only model wrapper.

Reference: ``megatron/model/gpt_model.py`` — ``GPTModel`` wraps the
language model and ``post_language_model_processing`` (:21-41) turns
logits into the vocab-parallel CE loss (per-token; masking/averaging is the
entry point's loss_func, finetune.py:201-218).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import (
    MODEL_PARALLEL,
    TENSOR_PARALLEL,
    TransformerConfig,
    refusal,
)
from megatron_llm_tpu.models.language_model import (
    init_language_model_params,
    language_model_forward,
    language_model_param_specs,
    flops_per_token,
    lm_head_weight,
)
from megatron_llm_tpu.ops.cross_entropy import (
    fused_linear_cross_entropy,
    vocab_parallel_cross_entropy,
)


def _vocab_unsharded() -> bool:
    """True when the head is not vocab-sharded (no tp axis in play), so
    the fused chunked CE can slice the full weight locally."""
    from megatron_llm_tpu import topology

    try:
        return topology.get_tensor_model_parallel_world_size() == 1
    except RuntimeError:                  # mesh not initialized:
        return True                       # single-device path


def parallelism_in_force() -> tuple:
    """The features of ``config.RUNS_WITH`` that the mesh as it stands
    turns on: what a model is refused under is asked of the table."""
    from megatron_llm_tpu import topology

    tp = not _vocab_unsharded()
    pp = (topology.model_parallel_is_initialized()
          and topology.get_pipeline_model_parallel_world_size() > 1)
    return ((TENSOR_PARALLEL,) if tp else ()) + (
        (MODEL_PARALLEL,) if tp or pp else ())


class GPTModel:
    """Functional model: holds only the (hashable) config; params live in a
    pytree owned by the caller."""

    def __init__(self, cfg: TransformerConfig):
        from megatron_llm_tpu.models.moe import resolve_expert_axis

        # pin the MoE expert-dim placement to the mesh as it stands NOW, so
        # spec time and trace time agree even across a mesh re-init
        self.cfg = resolve_expert_axis(cfg)
        said = refusal(cfg, parallelism_in_force())
        if said:
            raise ValueError(said)

    # -- params ------------------------------------------------------------
    def init(self, key) -> dict:
        return init_language_model_params(key, self.cfg)

    def param_specs(self, params) -> dict:
        return language_model_param_specs(params, self.cfg)

    def num_params(self, params) -> int:
        return sum(x.size for x in jax.tree_util.tree_leaves(params))

    def flops_per_token(self, seq_len=None) -> float:
        return flops_per_token(self.cfg, seq_len)

    # -- forward -----------------------------------------------------------
    def __call__(
        self,
        params,
        tokens: jax.Array,
        position_ids: Optional[jax.Array] = None,
        attention_mask: Optional[jax.Array] = None,
        labels: Optional[jax.Array] = None,
        *,
        rng_key=None,
        train: bool = False,
        sequence_parallel: bool = False,
        kv_caches=None,
    ):
        """Returns per-token loss [b, s] when labels given, else logits
        [b, s, V] (reference: gpt_model.py:82-100)."""
        cfg = self.cfg
        moe_on = cfg.num_experts > 1
        if (labels is not None and kv_caches is None
                and cfg.fused_lm_cross_entropy and _vocab_unsharded()):
            # fused head+CE over vocab chunks: the [b, s, V] logits are
            # never materialized (ops/cross_entropy.py)
            h = language_model_forward(
                params, tokens, position_ids, attention_mask, cfg,
                rng_key=rng_key, train=train,
                sequence_parallel=sequence_parallel,
                compute_logits=False,
            )
            moe_aux = None
            if moe_on:
                h, moe_aux = h
            head = lm_head_weight(params)
            loss = fused_linear_cross_entropy(
                h, head.astype(cfg.compute_jnp_dtype), labels,
                chunk_size=cfg.fused_ce_chunk_size,
            )
            return (loss, moe_aux) if moe_on else loss
        out = language_model_forward(
            params, tokens, position_ids, attention_mask, self.cfg,
            rng_key=rng_key, train=train, sequence_parallel=sequence_parallel,
            kv_caches=kv_caches,
        )
        moe_aux = None
        if kv_caches is not None:
            logits, new_caches = out
        else:
            logits, new_caches = out, None
            if moe_on:
                logits, moe_aux = logits
        if labels is None:
            # generation: routing aux is irrelevant, drop it
            return (logits, new_caches) if kv_caches is not None else logits
        loss = vocab_parallel_cross_entropy(logits.astype(jnp.float32), labels)
        if kv_caches is not None:
            return loss, new_caches
        return (loss, moe_aux) if moe_on else loss
