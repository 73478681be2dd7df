"""Trinity wrapper (arcee-ai Trinity-Mini, ``model_type`` ``afmoe``).

Beyond the reference (which has neither MoE nor more than one kind of
layer): the assert-the-architecture-flags pattern of ``mellum.py`` /
``kanana.py`` for a llama-style trunk with Mellum's attention geometry
(32 query and 4 key-value heads of 128, three ``sliding`` layers to each
``full`` one, given as data in ``cfg.layer_types``) and Kanana's router
form, and four things of its own, each a field of ``TransformerConfig``
that a later model can set:

* **a gate on the attention output** (``attention_output_gate``): the
  heads' output times ``sigmoid(gate(u))`` before the output projection,
  ``gate`` a fourth projection of the layer's normed input, fused into
  ``query_key_value`` (``models/transformer.py::_split_qkv``);
* **four norms a layer** (``sublayer_output_norm``): each sublayer's
  output is normed as well as its input, ``x + norm(f(norm(x)))``.  HF's
  names are a trap: its ``post_attention_layernorm`` is the norm of the
  attention OUTPUT (here ``attention_output_norm``) and its
  ``pre_mlp_layernorm`` is this tree's ``post_attention_norm``;
* **the sliding layers rotate, the full ones carry no positions**
  (``rope_layer_types``): plain rotary, theta 10,000, on the window
  layers alone;
* **two leading dense layers INSIDE the typed stack**
  (``moe_first_dense_layers`` with ``layer_types``): a dense layer is of
  the type its index gives it and holds pages in that type's group, and
  the sparse layers start mid-period.

Per-head QK-norm, embeddings times ``sqrt(hidden_size)``
(``mup_enabled``), 128 experts of width 1,024 of which a token uses 8 by
sigmoid scores plus a choice bias, the gates renormalised and scaled,
one shared gated expert, no bias, untied head.

What these do not run with is rows of ``config.RUNS_WITH`` (``GATE``,
``OUTPUT_NORMS``, ``ROPE_TYPES``, before the row of its layer types).
"""

from __future__ import annotations

import math

from megatron_llm_tpu.config import PositionEmbeddingType, TransformerConfig
from megatron_llm_tpu.models.gpt import GPTModel


class TrinityModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert not cfg.tie_embed_logits
        assert cfg.num_experts > 1, "trinity is a sparse MoE model"
        assert cfg.norm_topk_prob, "trinity renormalises its chosen gates"
        assert cfg.moe_score_function == "sigmoid" and cfg.moe_choice_bias, \
            "trinity routes by sigmoid scores with a choice bias"
        assert cfg.moe_shared_experts > 0, "trinity has a shared expert"
        assert cfg.qk_norm_per_head, "trinity norms each query and key head"
        assert cfg.attention_output_gate, \
            "trinity gates its attention output (attention_output_gate)"
        assert cfg.sublayer_output_norm, \
            "trinity norms both sublayers' outputs (sublayer_output_norm)"
        assert cfg.layer_types is not None and cfg.rope_layer_types, \
            "trinity's layers are of two types (layer_types) of which " \
            "some rotate (rope_layer_types)"
        assert cfg.embedding_multiplier is not None, \
            "trinity scales its embeddings (mup_enabled)"
        super().__init__(cfg)


def trinity_config(size: str = "mini", **overrides) -> TransformerConfig:
    shapes = {
        # two dense layers, so the six sparse ones start mid-period
        "tiny": dict(num_layers=8, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=2, kv_channels=32,
                     ffn_hidden_size=256, moe_ffn_hidden_size=64,
                     padded_vocab_size=512, num_experts=8, moe_top_k=4,
                     sliding_window_size=16,
                     seq_length=256, max_position_embeddings=1024),
        "mini": dict(num_layers=32, hidden_size=2048,
                     num_attention_heads=32, num_attention_heads_kv=4,
                     kv_channels=128, ffn_hidden_size=6144,
                     moe_ffn_hidden_size=1024, padded_vocab_size=200192,
                     num_experts=128, moe_top_k=8),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.rotary,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-5,
        add_bias_linear=False,
        tie_embed_logits=False,
        norm_topk_prob=True,
        moe_score_function="sigmoid",
        moe_choice_bias=True,
        moe_routed_scale=2.826,
        moe_shared_experts=1,
        moe_first_dense_layers=2,
        qk_norm_per_head=True,
        attention_output_gate=True,
        sublayer_output_norm=True,
        rope_theta=10000.0,
        sliding_window_size=2048,
        layer_types=("sliding", "sliding", "sliding", "full"),
        rope_layer_types=("sliding",),
        seq_length=32768,
        max_position_embeddings=131072,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    base.setdefault("embedding_multiplier", math.sqrt(base["hidden_size"]))
    return TransformerConfig(**base)
