"""Keye wrapper (the language model of Keye-VL-2.0-30B-A3B).

Beyond the reference (which has neither MoE nor sparse attention): the
assert-the-architecture-flags pattern of ``olmoe.py`` for ``model_type``
``KeyeVL2``'s language model: a llama-style trunk with grouped-query
attention (32 query and 4 key-value heads of 128 over a hidden size of
2048), RMSNorm on every query and key HEAD before the rotary embedding
(``qk_norm_per_head``), the rotary frequencies dealt to three position
streams (``rope_sections``; a text token's three positions coincide),
128 small experts a layer of which a token uses 8 with gates
renormalised to sum to 1, and learned sparse attention
(``dsa_index_heads``: an indexer of 16 heads of 64 scores every earlier
position and the query attends its 2,048 best).  The vision tower is
not built: requests are text.  No shared expert, no bias, untied head.

What sparse attention does not run with (tensor parallelism: the
indexer's one key head and the per-query choice are not sharded) is a
row of ``config.RUNS_WITH``.
"""

from __future__ import annotations

from megatron_llm_tpu.config import (
    TENSOR_PARALLEL,
    PositionEmbeddingType,
    TransformerConfig,
    refusal,
)
from megatron_llm_tpu.models.gpt import GPTModel, _vocab_unsharded


class KeyeModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert not cfg.tie_embed_logits
        assert cfg.num_experts > 1, "keye is a sparse MoE model"
        assert cfg.norm_topk_prob, "keye renormalises its chosen gates"
        assert cfg.qk_norm_per_head, \
            "keye normalises each query and key head"
        assert cfg.dsa_index_heads > 0, \
            "keye attends through its sparse-attention indexer"
        assert cfg.sliding_window_size is None
        # asked of the mesh under this module's own name (GPTModel asks
        # again under its own): tests stand a sharded mesh in here
        if not _vocab_unsharded():
            raise ValueError(refusal(cfg, (TENSOR_PARALLEL,)))
        super().__init__(cfg)


def keye_config(size: str = "30B-A3B", **overrides) -> TransformerConfig:
    shapes = {
        "tiny": dict(num_layers=2, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=2, kv_channels=32,
                     ffn_hidden_size=256, moe_ffn_hidden_size=64,
                     padded_vocab_size=512,
                     num_experts=8, moe_top_k=4, dsa_index_heads=4,
                     dsa_index_head_dim=16, dsa_topk=8,
                     rope_sections=(4, 6, 6),
                     seq_length=256, max_position_embeddings=512),
        "30B-A3B": dict(num_layers=48, hidden_size=2048,
                        num_attention_heads=32, num_attention_heads_kv=4,
                        kv_channels=128, ffn_hidden_size=6144,
                        moe_ffn_hidden_size=768,
                        padded_vocab_size=151936, num_experts=128,
                        moe_top_k=8, dsa_index_heads=16,
                        dsa_index_head_dim=64, dsa_topk=2048,
                        rope_sections=(16, 24, 24)),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.rotary,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-6,
        add_bias_linear=False,
        tie_embed_logits=False,
        qk_norm_per_head=True,
        norm_topk_prob=True,
        rope_theta=1e7,
        seq_length=32768,
        max_position_embeddings=262144,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
