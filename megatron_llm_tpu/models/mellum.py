"""Mellum wrapper (Mellum 2, ``model_type`` ``mellum``).

Beyond the reference (which has neither MoE nor more than one kind of
layer): the assert-the-architecture-flags pattern of ``olmoe.py`` /
``keye.py`` for a llama-style trunk whose layers are of TWO types, given
as data (``cfg.layer_types``, one period: three ``sliding`` layers to
each ``full`` one).  A sliding layer's query attends the
``sliding_window_size`` keys up to itself under the plain rotary
embedding; a full layer's attends every key up to itself under YaRN
(``rope_yarn_scaling`` on ``rope_yarn_layer_types``).  Grouped-query
attention (32 query and 4 key-value heads of 128 over a hidden size of
2304), no QK-norm, 64 small experts a layer of which a token uses 8 with
gates renormalised to sum to 1.  No shared expert, no bias, untied head.
The published multi-token-prediction head is not built.

What a layer type per layer does not run with is a row of
``config.RUNS_WITH`` (tensor parallelism was not tried; a pipeline
stage's layers would need the pattern's phase, and
``parallel/pipeline.py`` gives its layers no type); the legacy rolling
decode cache asserts a window on every layer.
"""

from __future__ import annotations

from megatron_llm_tpu.config import (
    MODEL_PARALLEL,
    PositionEmbeddingType,
    TransformerConfig,
    refusal,
)
from megatron_llm_tpu.models.gpt import GPTModel, _vocab_unsharded


class MellumModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert not cfg.tie_embed_logits
        assert cfg.num_experts > 1, "mellum is a sparse MoE model"
        assert cfg.norm_topk_prob, "mellum renormalises its chosen gates"
        assert not (cfg.qk_norm or cfg.qk_norm_per_head)
        assert cfg.layer_types is not None, \
            "mellum's layers are of two types (layer_types)"
        # asked of the mesh under this module's own name (GPTModel asks
        # again under its own, and of the pipeline): tests stand a
        # sharded mesh in here
        if not _vocab_unsharded():
            raise ValueError(refusal(cfg, (MODEL_PARALLEL,)))
        super().__init__(cfg)


def mellum_config(size: str = "12B-A2.5B", **overrides) -> TransformerConfig:
    shapes = {
        "tiny": dict(num_layers=8, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=2, kv_channels=32,
                     ffn_hidden_size=256, moe_ffn_hidden_size=64,
                     padded_vocab_size=512, num_experts=8, moe_top_k=4,
                     sliding_window_size=16,
                     rope_yarn_scaling=(16.0, 64, 32.0, 1.0,
                                        1.2772588722239782),
                     seq_length=256, max_position_embeddings=1024),
        "12B-A2.5B": dict(num_layers=28, hidden_size=2304,
                          num_attention_heads=32, num_attention_heads_kv=4,
                          kv_channels=128, ffn_hidden_size=7168,
                          moe_ffn_hidden_size=896,
                          padded_vocab_size=98304, num_experts=64,
                          moe_top_k=8),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.rotary,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-6,
        add_bias_linear=False,
        tie_embed_logits=False,
        norm_topk_prob=True,
        rope_theta=500000.0,
        sliding_window_size=1024,
        layer_types=("sliding", "sliding", "sliding", "full"),
        rope_yarn_scaling=(16.0, 8192, 32.0, 1.0, 1.2772588722239782),
        rope_yarn_layer_types=("full",),
        seq_length=32768,
        max_position_embeddings=131072,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
