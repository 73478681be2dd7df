"""Ouro wrapper (ByteDance Ouro-2.6B, ``model_type`` ``ouro``).

Beyond the reference (whose stack runs once): the
assert-the-architecture-flags pattern of ``mistral.py`` / ``trinity.py``
for a llama-style trunk (rotary at theta 1e6 over the whole head, a gated
MLP, RMSNorm, no bias, an untied head, as many key-value heads as query
heads) with Trinity's four norms a layer (``sublayer_output_norm``) and
one thing of its own, a field of ``TransformerConfig`` that a later
model can set:

* **the stack is LOOPED** (``loop_steps``, the published
  ``total_ut_steps``): the ``num_layers`` layers run ``loop_steps`` times
  over the SAME weights, the final norm after EACH pass (its output is
  the next pass's input and, after the last, the head's), and pass t's
  attention reads the keys and values that pass t wrote, never another
  pass's: a token holds ``cfg.cache_layers = num_layers x loop_steps``
  planes (``ops/paged_kv.py::init_pools``; a page id names its tokens in
  all of them).  Nothing else depends on the pass.

After each pass an exit gate (``exit_gate`` of the stack's params,
``hidden_size + 1`` parameters) reads the normed stream; the exit
distribution (``exit_distribution`` here, over the program's full
forward) says at which pass a token WOULD leave at a threshold.  What
runs is the published ``early_exit_threshold`` 1.0: every token runs
every pass.  A threshold under 1 is refused at
``TransformerConfig.__post_init__``; sharing one pass's planes between
passes at decode time is a different model output and is not built;
training is refused.  What a looped stack does not run with is the row
``LOOPED`` of ``config.RUNS_WITH``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import PositionEmbeddingType, TransformerConfig
from megatron_llm_tpu.models.gpt import GPTModel
from megatron_llm_tpu.models.language_model import embedding_forward
from megatron_llm_tpu.models.transformer import (
    exit_distribution as _exit_distribution, rotary_freqs, transformer_stack)


class OuroModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert not cfg.tie_embed_logits
        assert cfg.sublayer_output_norm, \
            "ouro norms both sublayers' outputs (sublayer_output_norm)"
        assert cfg.num_attention_heads_kv == cfg.num_attention_heads, \
            "ouro has a key-value head a query head"
        assert cfg.loop_steps > 1, \
            "ouro runs its layers several times (loop_steps)"
        super().__init__(cfg)


def exit_distribution(model: GPTModel, params, tokens: jax.Array):
    """``[loop_steps, b, s]`` float32: the share of each token's exit
    mass at each pass, from the program's full forward (no cache) over
    ``tokens`` ``[b, s]``; sums to 1 over the passes.  A token leaves at
    the first pass where the running sum reaches
    ``cfg.early_exit_threshold``: at 1.0, the last."""
    cfg = model.cfg
    b, s = tokens.shape
    position_ids = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    h = embedding_forward(tokens, position_ids, params["embedding"], cfg,
                          train=False)
    _, gates = transformer_stack(
        h, params["transformer"], cfg, freqs=rotary_freqs(cfg),
        position_ids=position_ids, train=False, return_exit=True)
    return _exit_distribution(gates)


def ouro_config(size: str = "2.6B", **overrides) -> TransformerConfig:
    shapes = {
        "tiny": dict(num_layers=3, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=4, kv_channels=32,
                     ffn_hidden_size=256, padded_vocab_size=512,
                     seq_length=256, max_position_embeddings=1024),
        "2.6B": dict(num_layers=48, hidden_size=2048,
                     num_attention_heads=16, num_attention_heads_kv=16,
                     kv_channels=128, ffn_hidden_size=5632,
                     padded_vocab_size=49152),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.rotary,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-6,
        add_bias_linear=False,
        tie_embed_logits=False,
        sublayer_output_norm=True,
        loop_steps=4,
        rope_theta=1e6,
        seq_length=4096,
        max_position_embeddings=65536,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
