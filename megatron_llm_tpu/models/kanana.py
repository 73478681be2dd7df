"""Kanana wrapper (kanana-2-30b-a3b, ``model_type`` ``deepseek_v3``).

Beyond the reference (which has neither MoE nor latent attention): the
assert-the-architecture-flags pattern of ``olmoe.py`` / ``keye.py`` /
``mellum.py`` for DeepSeek-V3's layer at this model's size:

* **latent attention** (``cfg.kv_lora_rank``): keys and values of 32
  heads are expanded from one RMSNorm'd latent of 512 a token, and ONE
  rotary key head of 64 is shared by every query head; a query head is
  128 + 64 wide (only the 64 rotate), a value head 128.  The paged cache
  holds the latent and the rotary key (``ops/paged_kv.py``), and the
  engine's programs attend it in the absorbed form
  (``models/transformer.py::latent_attention``);
* **a sigmoid router** (``moe_score_function``) whose top-6 of 128 is
  chosen over score + a bias an expert (``moe_choice_bias``) while the
  gates are the scores, renormalised and scaled
  (``moe_routed_scale``);
* **two shared experts**: one ungated MLP of 2 x 768 beside the routed
  sum (``moe_shared_experts``);
* **one leading dense layer** of width 6144
  (``moe_first_dense_layers``), stacked apart from the sparse ones.

The query is ONE projection (``q_lora_rank`` null as published; a
compressed query is built where a config sets it, ``models/glm5.py``),
no group-limited routing, no multi-token-prediction head, no bias,
untied head.

What these do not run with is rows of ``config.RUNS_WITH`` (the latent
and the one rotary key head are not sharded, and a pipeline stage's
layers are taken to be of one kind); the legacy rolling / contiguous
decode caches are refused by ``latent_attention`` itself.
"""

from __future__ import annotations

from megatron_llm_tpu.config import TransformerConfig, PositionEmbeddingType
from megatron_llm_tpu.models.gpt import GPTModel


class KananaModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert not cfg.tie_embed_logits
        assert cfg.num_experts > 1, "kanana is a sparse MoE model"
        assert cfg.norm_topk_prob, "kanana renormalises its chosen gates"
        assert cfg.latent_attention, \
            "kanana attends through a latent (kv_lora_rank)"
        assert cfg.moe_score_function == "sigmoid" and cfg.moe_choice_bias, \
            "kanana routes by sigmoid scores with a choice bias"
        assert cfg.moe_shared_experts > 0, "kanana has shared experts"
        assert cfg.sliding_window_size is None
        super().__init__(cfg)


def kanana_config(size: str = "30B-A3B", **overrides) -> TransformerConfig:
    shapes = {
        # one dense layer, a shared expert, a latent (32) narrower than
        # the heads' total (4 x 16)
        "tiny": dict(num_layers=3, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=4, kv_channels=16,
                     ffn_hidden_size=256, moe_ffn_hidden_size=64,
                     padded_vocab_size=512, num_experts=8, moe_top_k=3,
                     moe_shared_experts=1, kv_lora_rank=32,
                     qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                     seq_length=256, max_position_embeddings=512),
        "30B-A3B": dict(num_layers=48, hidden_size=2048,
                        num_attention_heads=32, num_attention_heads_kv=32,
                        kv_channels=128, ffn_hidden_size=6144,
                        moe_ffn_hidden_size=768, padded_vocab_size=128256,
                        num_experts=128, moe_top_k=6, moe_shared_experts=2,
                        kv_lora_rank=512, qk_nope_head_dim=128,
                        qk_rope_head_dim=64, v_head_dim=128),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.rotary,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-6,
        add_bias_linear=False,
        tie_embed_logits=False,
        norm_topk_prob=True,
        moe_score_function="sigmoid",
        moe_choice_bias=True,
        moe_routed_scale=2.448,
        moe_first_dense_layers=1,
        rope_theta=1e6,
        seq_length=32768,
        max_position_embeddings=32768,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
