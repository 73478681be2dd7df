"""Nemotron-H wrapper (NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type``
``nemotron_h``).

Beyond the reference (which has neither MoE nor a state-space layer): the
assert-the-architecture-flags pattern of ``granite.py`` / ``kanana.py``
for a hybrid whose LAYERS ARE ONE SUBLAYER EACH:

* **a layer kind per layer**, given as data (``cfg.layer_types``, read
  from the published ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer,
  ``*`` an attention mixer, ``E`` an expert layer ALONE): a layer is
  ``x + f(norm(x))`` under ONE norm, so a mixer layer has no MLP and an
  expert layer no mixer and no cache entry (``cfg.one_sublayer``;
  ``models/transformer.py::transformer_layer``).  The three kinds'
  parameters are stacked apart (``layers['mamba']``,
  ``layers['attention']``, ``layers['moe']``) under the one norm every
  layer has.  The published pattern does not repeat, so the whole depth
  is one period;
* **a Mamba-2 mixer of several groups** (``mamba_n_groups`` 8): eight
  heads share each ``B_t``, ``C_t``, and the gated RMSNorm runs over each
  group's channels apart (``models/mamba.py``); its inner width is
  ``mamba_n_heads * mamba_d_head``, not a multiple of the hidden size;
* **attention with no position embedding**
  (``PositionEmbeddingType.none``; the published ``rope_theta`` rotates
  nothing), 16 query heads a key/value head;
* **ungated experts** ``relu(x W_up)^2 W_down`` (``mlp_activation``
  ``relu2``: two matrices, no gate) and a shared MLP of the same form,
  under **a sigmoid router** with a choice bias and a scale
  (``moe_score_function``, ``moe_choice_bias``, ``moe_routed_scale``) of
  which one chip may hold A SHARE (``moe_router_experts``).

Untied head, RMSNorm, no bias but the convolution's.

What such a stack does not run with is a row of ``config.RUNS_WITH``
(``ONE_SUBLAYER``, before the rows of its state-space layers and of its
layer types).
"""

from __future__ import annotations

from megatron_llm_tpu.config import (
    PositionEmbeddingType,
    TransformerConfig,
    pattern_layer_types,
)
from megatron_llm_tpu.models.gpt import GPTModel

# the published 52 layers: five times MEMEM*E, then MEMEMEM*E, then
# MEMEMEME (23 mixers of state-space kind, 23 expert layers, 6 attention)
NANO_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


class NemotronHModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.none, \
            "nemotron_h's attention has no position embedding"
        assert cfg.glu_activation is None and cfg.mlp_activation == "relu2", \
            "nemotron_h's MLPs are ungated relu^2"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert not cfg.tie_embed_logits
        assert cfg.one_sublayer, \
            "nemotron_h's layers are one sublayer each: 'mamba', " \
            "'attention' and 'moe' (hybrid_override_pattern)"
        assert cfg.num_experts > 1, "nemotron_h's 'moe' layers are sparse"
        assert cfg.norm_topk_prob, "nemotron_h renormalises its chosen gates"
        assert cfg.moe_score_function == "sigmoid" and cfg.moe_choice_bias, \
            "nemotron_h routes by sigmoid scores with a choice bias"
        assert cfg.moe_shared_experts > 0, "nemotron_h has a shared MLP"
        assert cfg.sliding_window_size is None
        super().__init__(cfg)


def nemotron_h_config(size: str = "nano-30b-a3b",
                      **overrides) -> TransformerConfig:
    shapes = {
        # two groups of heads; an expert's width (96) that no multiple of
        # 128 divides; half of the router's eight experts held
        "tiny": dict(num_layers=14, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=2, kv_channels=32,
                     ffn_hidden_size=96, padded_vocab_size=512,
                     num_experts=4, moe_router_experts=8, moe_top_k=3,
                     moe_shared_experts=2,
                     layer_types=pattern_layer_types("MEMEM*EMEMEM*E"),
                     mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                     mamba_n_groups=2, mamba_chunk_size=16,
                     seq_length=256, max_position_embeddings=512),
        "nano-30b-a3b": dict(num_layers=52, hidden_size=2688,
                             num_attention_heads=32,
                             num_attention_heads_kv=2, kv_channels=128,
                             ffn_hidden_size=1856, padded_vocab_size=131072,
                             num_experts=128, moe_top_k=6,
                             moe_shared_experts=2,
                             layer_types=pattern_layer_types(NANO_PATTERN),
                             mamba_n_heads=64, mamba_d_head=64,
                             mamba_d_state=128, mamba_n_groups=8,
                             mamba_chunk_size=128),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.none,
        glu_activation=None,
        mlp_activation="relu2",
        normalization="rmsnorm",
        layernorm_epsilon=1e-5,
        add_bias_linear=False,
        tie_embed_logits=False,
        norm_topk_prob=True,
        moe_score_function="sigmoid",
        moe_choice_bias=True,
        moe_routed_scale=2.5,
        mamba_d_conv=4,
        mamba_conv_bias=True,
        seq_length=262144,
        max_position_embeddings=262144,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
