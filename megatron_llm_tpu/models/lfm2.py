"""LFM2-8B-A1B (LiquidAI, ``model_type`` ``lfm2_moe``): the assert-the-flags
wrapper that ``granite.py`` and ``trinity.py`` are.

What the family is, as data of ``TransformerConfig`` (nothing in the
engine, the scheduler or the cache names it):

* a layer type per layer, of TWO mixer kinds: ``conv``, a gated short
  convolution (``models/short_conv.py``: ``[B | C | X] = u W_in``, a
  causal depthwise convolution of ``conv_taps`` 3 taps over ``B * X``
  with no bias and no activation, ``(C * c) W_out``; what a request
  carries is two columns of the hidden width a layer), beside
  ``attention`` (the published ``full_attention``): 32 query heads over 8
  key-value heads of 64, each head's 64 values RMSNorm'd by themselves
  (``qk_norm_per_head``), rotated at theta 1e6.  The published pattern
  does not repeat (attention at layers 2, 6, 10, 14, 18 and 21 of 24), so
  ``layer_types`` spells out every layer of the depth;
* two norms a layer (``operator_norm`` is the tree's ``input_norm``,
  ``ffn_norm`` its ``post_attention_norm``), RMSNorm eps 1e-5;
* ``moe_first_dense_layers`` 2: layers 0 and 1 (both ``conv``) keep a
  dense SwiGLU MLP of ``ffn_hidden_size`` 7,168; their MIXERS are members
  of the ``conv`` stack like any other layer's
  (``transformer.py::init_stack_params``);
* the other layers 32 SwiGLU experts of 1,792, four a token, no shared
  expert, routed by ``sigmoid`` scores in float32, the choice over score
  + ``expert_bias`` (``moe_choice_bias``), the gates the chosen scores
  over ``their sum + 1e-6`` (``moe_gate_norm_eps`` /
  ``moe_gate_norm_added``), times ``routed_scaling_factor`` 1.0;
* a tied head under the stack's final norm (the published
  ``embedding_norm``, which is applied LAST).
"""

from __future__ import annotations

from megatron_llm_tpu.config import PositionEmbeddingType, TransformerConfig
from megatron_llm_tpu.models.gpt import GPTModel

# the published layer_types, 'full_attention' as the tree's 'attention'
PUBLISHED_LAYER_TYPES = tuple(
    "attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


class Lfm2Model(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert cfg.tie_embed_logits, "lfm2's head is its embedding"
        assert cfg.short_conv, \
            "lfm2's layers are 'conv' and 'attention' (layer_types)"
        assert cfg.rope_layer_types is None, \
            "lfm2's attention layers all rotate"
        assert cfg.qk_norm_per_head, "lfm2 norms each query and key head"
        assert cfg.num_experts > 1, "lfm2_moe is a sparse MoE model"
        assert cfg.moe_shared_experts == 0, "lfm2_moe has no shared expert"
        assert cfg.norm_topk_prob and cfg.moe_gate_norm_added, \
            "lfm2's gates are the chosen scores over their sum + epsilon"
        assert cfg.moe_score_function == "sigmoid" and cfg.moe_choice_bias, \
            "lfm2 routes by sigmoid scores with a choice bias"
        super().__init__(cfg)


def lfm2_config(size: str = "8b-a1b", **overrides) -> TransformerConfig:
    shapes = {
        # two dense layers and six sparse ones, attention where the
        # published stack's first eight layers have it; heads of 64 as
        # published, so that the pool's two-heads-a-row layout is run
        "tiny": dict(num_layers=8, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=2, kv_channels=64,
                     ffn_hidden_size=256, moe_ffn_hidden_size=64,
                     padded_vocab_size=512, num_experts=8, moe_top_k=4,
                     layer_types=PUBLISHED_LAYER_TYPES[:8],
                     seq_length=256, max_position_embeddings=1024),
        "8b-a1b": dict(num_layers=24, hidden_size=2048,
                       num_attention_heads=32, num_attention_heads_kv=8,
                       kv_channels=64, ffn_hidden_size=7168,
                       moe_ffn_hidden_size=1792, padded_vocab_size=65536,
                       num_experts=32, moe_top_k=4,
                       layer_types=PUBLISHED_LAYER_TYPES),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.rotary,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-5,
        add_bias_linear=False,
        tie_embed_logits=True,
        norm_topk_prob=True,
        moe_score_function="sigmoid",
        moe_choice_bias=True,
        moe_routed_scale=1.0,
        moe_gate_norm_eps=1e-6,
        moe_gate_norm_added=True,
        moe_first_dense_layers=2,
        qk_norm_per_head=True,
        conv_taps=3,
        conv_mixer_bias=False,
        rope_theta=1000000.0,
        seq_length=32768,
        max_position_embeddings=128000,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
