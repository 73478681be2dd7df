"""Granite 4.0-H wrapper (granite-4.0-h-small, ``model_type``
``granitemoehybrid``).

Beyond the reference (which has neither MoE nor a state-space layer): the
assert-the-architecture-flags pattern of ``mellum.py`` / ``kanana.py``
for a HYBRID stack:

* **a mixer kind per layer**, given as data (``cfg.layer_types``, one
  period of ``mamba`` and ``attention``: nine Mamba-2 mixers to each
  attention layer in the published model): ``models/mamba.py`` has the
  state-space mixer, whose per-request state is two arrays a slot (the
  ``state`` group of ``ops/paged_kv.py``), and the two kinds' parameters
  are stacked apart (``models/transformer.py::init_stack_params``);
* **attention with no position embedding**
  (``PositionEmbeddingType.none``: nothing rotates, nothing is added) and
  scores times ``attention_multiplier`` (1/128 at the published size, not
  1/sqrt(128));
* **four multipliers**: the embedding times ``embedding_multiplier``,
  both residual branches times ``residual_multiplier``, the logits
  divided by ``logits_scaling``;
* **experts with a shared MLP**: softmax routing over the chosen
  (``norm_topk_prob``: the softmax over the ten chosen logits is the
  renormalised softmax over all), a shared ungated MLP beside the routed
  sum, and, on one chip of a deployment that spreads a layer's experts,
  A SHARE of them (``moe_router_experts``: the router scores all, the
  layer holds ``num_experts``; ``models/moe.py``).

Tied head, RMSNorm, no bias but the convolution's.

What state-space layers do not run with is rows of
``config.RUNS_WITH``.  Refused inside the mechanism: training and an
explicit attention mask (``transformer_layer``: no backward through the
chunked scan is held to anything, and packed documents would need the
state reset), the legacy decode caches (``mamba_mixer``).
"""

from __future__ import annotations

from megatron_llm_tpu.config import TransformerConfig, PositionEmbeddingType
from megatron_llm_tpu.models.gpt import GPTModel


class GraniteModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.none, \
            "granite's attention has no position embedding"
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert cfg.tie_embed_logits
        assert cfg.state_space, \
            "granite's layers are 'mamba' and 'attention' (layer_types)"
        assert cfg.num_experts > 1, "granite-h-small is a sparse MoE model"
        assert cfg.norm_topk_prob, "granite's gates are a softmax over " \
            "the chosen logits"
        assert cfg.moe_shared_experts > 0, "granite has a shared MLP"
        assert cfg.sliding_window_size is None
        super().__init__(cfg)


def granite_config(size: str = "h-small", **overrides) -> TransformerConfig:
    shapes = {
        # two periods of (mamba, mamba, attention, mamba); half of the
        # router's eight experts held
        "tiny": dict(num_layers=8, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=2, kv_channels=32,
                     ffn_hidden_size=64, padded_vocab_size=512,
                     num_experts=4, moe_router_experts=8, moe_top_k=3,
                     moe_shared_experts=2,
                     layer_types=("mamba", "mamba", "attention", "mamba"),
                     mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16,
                     mamba_chunk_size=16, attention_multiplier=1.0 / 32,
                     seq_length=256, max_position_embeddings=512),
        "h-small": dict(num_layers=40, hidden_size=4096,
                        num_attention_heads=32, num_attention_heads_kv=8,
                        kv_channels=128, ffn_hidden_size=768,
                        padded_vocab_size=100352, num_experts=72,
                        moe_top_k=10, moe_shared_experts=2,
                        layer_types=("mamba",) * 5 + ("attention",)
                        + ("mamba",) * 4,
                        mamba_n_heads=128, mamba_d_head=64,
                        mamba_d_state=128, mamba_chunk_size=256,
                        attention_multiplier=0.0078125),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.none,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-5,
        add_bias_linear=False,
        tie_embed_logits=True,
        norm_topk_prob=True,
        mamba_n_groups=1,
        mamba_d_conv=4,
        mamba_conv_bias=True,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        seq_length=131072,
        max_position_embeddings=131072,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
