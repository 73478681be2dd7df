"""Qwen3-Next wrapper (Qwen's Qwen3-Next-80B-A3B, ``model_type``
``qwen3_next``).

Beyond the reference (which has neither MoE nor more than one kind of
layer): the assert-the-architecture-flags pattern of ``granite.py`` /
``trinity.py`` for a llama-style trunk whose layers are of two kinds
given as data in ``cfg.layer_types``, three ``gated_delta`` layers
(``models/gated_delta.py``: a state a value head that is updated by what
it holds, behind a convolution of four taps) to every ``attention``
layer, and in every layer experts chosen ten of 512 by a softmax router
beside ONE shared expert under a sigmoid gate of its own
(``moe_shared_expert_gate``).  An attention layer has 16 query heads and
2 key-value heads of 256, each normed by itself (``qk_norm_per_head``),
rotating in its first quarter only (``rotary_percent`` 0.25, theta 1e7:
``ops/rope.py::apply_rotary_at``'s ``rot_d``), its output under a
sigmoid gate from a fourth projection (``attention_output_gate``).  No
bias, an untied head, RMSNorm of eps 1e-6.

THE NORMS' SCALE.  The published RMSNorm computes ``x_hat * (1 + w)``
with ``w`` drawn at zero; the program holds ``1 + w`` as its scale (as
``models/gemma.py`` does: the same sums), drawn at one, and a
checkpoint's conversion adds the one.  The gated norm inside a delta
layer is the exception there and here: its scale is ``w`` itself.

What these do not run with is the row ``GATED_DELTA`` of
``config.RUNS_WITH`` (before ``GATE``'s and ``TYPED``'s).
"""

from __future__ import annotations

from megatron_llm_tpu.config import PositionEmbeddingType, TransformerConfig
from megatron_llm_tpu.models.gpt import GPTModel

PERIOD = ("gated_delta", "gated_delta", "gated_delta", "attention")


class Qwen3NextModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert not cfg.tie_embed_logits
        assert cfg.gated_delta and "attention" in cfg.layer_types, \
            "qwen3_next's layers are 'gated_delta' and 'attention' " \
            "(layer_types)"
        assert cfg.num_experts > 1, "qwen3_next is a sparse MoE model"
        assert cfg.norm_topk_prob and cfg.moe_score_function == "softmax", \
            "qwen3_next renormalises the chosen gates of a softmax router"
        assert cfg.moe_shared_experts > 0 and cfg.moe_shared_expert_gate, \
            "qwen3_next has a shared expert under its own gate " \
            "(moe_shared_expert_gate)"
        assert cfg.qk_norm_per_head, \
            "qwen3_next norms each query and key head"
        assert cfg.attention_output_gate, \
            "qwen3_next gates its attention output (attention_output_gate)"
        super().__init__(cfg)


def qwen3_next_config(size: str = "80b-a3b", **overrides) -> TransformerConfig:
    shapes = {
        # two periods; a share of the experts (8 of 16) and a partial
        # rotary (8 of 32 dimensions), so that both are run
        "tiny": dict(num_layers=8, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=2, kv_channels=32,
                     ffn_hidden_size=256, moe_ffn_hidden_size=64,
                     padded_vocab_size=512, num_experts=8,
                     moe_router_experts=16, moe_top_k=4,
                     delta_key_heads=2, delta_value_heads=4,
                     delta_key_dim=16, delta_value_dim=16,
                     seq_length=256, max_position_embeddings=1024),
        "80b-a3b": dict(num_layers=48, hidden_size=2048,
                        num_attention_heads=16, num_attention_heads_kv=2,
                        kv_channels=256, ffn_hidden_size=5120,
                        moe_ffn_hidden_size=512, padded_vocab_size=151936,
                        num_experts=512, moe_top_k=10,
                        delta_key_heads=16, delta_value_heads=32,
                        delta_key_dim=128, delta_value_dim=128),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.rotary,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-6,
        add_bias_linear=False,
        tie_embed_logits=False,
        norm_topk_prob=True,
        moe_shared_experts=1,
        moe_shared_expert_gate=True,
        qk_norm_per_head=True,
        attention_output_gate=True,
        rotary_percent=0.25,
        rope_theta=10000000.0,
        delta_conv_taps=4,
        layer_types=PERIOD,
        seq_length=32768,
        max_position_embeddings=262144,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
