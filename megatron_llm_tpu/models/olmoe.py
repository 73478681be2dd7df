"""OLMoE wrapper (sparse MoE with QK-norm).

Beyond the reference (which has neither MoE nor OLMoE): the same
assert-the-architecture-flags pattern as ``mixtral.py``, for the
OLMoE-1B-7B family (``model_type`` ``olmoe``) — llama-style trunk with
as many key-value heads as query heads, RMSNorm over the whole query and
key projections before the rotary embedding (``qk_norm``), and 64 small
experts a layer of which a token uses 8, weighted by the router's
softmax over all experts as it is (``norm_topk_prob`` false: the gates of
a token sum to less than 1).  No shared expert, no bias, untied head.
"""

from __future__ import annotations

from megatron_llm_tpu.config import TransformerConfig, PositionEmbeddingType
from megatron_llm_tpu.models.gpt import GPTModel


class OlmoeModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert not cfg.tie_embed_logits
        assert cfg.num_experts > 1, "olmoe is a sparse MoE model"
        assert cfg.qk_norm, "olmoe normalises its queries and keys"
        assert not cfg.norm_topk_prob, \
            "olmoe uses the router's gates as they are"
        assert cfg.sliding_window_size is None
        super().__init__(cfg)


def olmoe_config(size: str = "1B-7B", **overrides) -> TransformerConfig:
    shapes = {
        "tiny": dict(num_layers=2, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=4, ffn_hidden_size=64,
                     padded_vocab_size=512, num_experts=8, moe_top_k=4,
                     seq_length=256, max_position_embeddings=512),
        "1B-7B": dict(num_layers=16, hidden_size=2048,
                      num_attention_heads=16, num_attention_heads_kv=16,
                      ffn_hidden_size=1024, padded_vocab_size=50304,
                      num_experts=64, moe_top_k=8),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.rotary,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-5,
        add_bias_linear=False,
        tie_embed_logits=False,
        qk_norm=True,
        norm_topk_prob=False,
        rope_theta=10000.0,
        seq_length=4096,
        max_position_embeddings=4096,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
