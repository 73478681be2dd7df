"""GLM-5 wrapper (zai-org/GLM-5, ``model_type`` ``glm_moe_dsa``).

Beyond the reference (which has neither MoE, latent attention nor sparse
attention): the assert-the-architecture-flags pattern of ``kanana.py`` /
``keye.py`` for the layer that composes the two:

* **latent attention behind a compressed query** (``cfg.kv_lora_rank``,
  ``cfg.q_lora_rank``): the queries of 64 heads of 192 + 64 come from the
  normed input through two projections (6144 -> 2048 -> 64 x 256) with an
  RMSNorm between; keys and values (192 + 256 a head) are expanded from
  one RMSNorm'd latent of 512 a token, and ONE rotary key head of 64 is
  shared by every query head;
* **the selection INSIDE latent attention** (``cfg.dsa_index_heads``):
  an indexer of 32 heads of 128 whose queries read the COMPRESSED query
  (``dsa_index_query``) and of whose head only the first 64 dimensions
  rotate (``dsa_index_rope_dim``) scores every earlier position against
  one LayerNorm'd key of 128 a token, and each query attends the latents
  of its 2,048 best.  The paged cache holds two arrays a layer, the
  latent rows and the indexer's keys (``ops/paged_kv.py``), and the
  engine's programs attend the chosen rows in latent attention's two
  forms (``models/transformer.py::latent_attention``);
* **a sigmoid router with a choice bias** over 256 experts of width
  2048, 8 a token, gates renormalised and scaled by 2.5, beside ONE
  ungated shared expert, after three leading dense layers of 12288
  (Kanana's router form, ``models/moe.py``).

The published multi-token-prediction layer (``num_nextn_predict_layers``
1) is not built: a served request's next-token logits do not read it,
and drafting with it needs the verify step, which neither sparse nor
latent attention runs with.  No group-limited routing (``n_group`` 1), no
bias, untied head.

What the pair does not run with is rows of ``config.RUNS_WITH``
(``SPARSE``'s, ``LATENT``'s and ``SPARSE_LATENT``'s: the verify step,
the int8 pool, the host tier, tensor or pipeline parallelism, training).
"""

from __future__ import annotations

from megatron_llm_tpu.config import TransformerConfig, PositionEmbeddingType
from megatron_llm_tpu.models.gpt import GPTModel


class Glm5Model(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear
        assert not cfg.tie_embed_logits
        assert cfg.num_experts > 1, "glm5 is a sparse MoE model"
        assert cfg.norm_topk_prob, "glm5 renormalises its chosen gates"
        assert cfg.latent_attention and cfg.q_lora_rank is not None, \
            "glm5 attends through a latent behind a compressed query " \
            "(kv_lora_rank, q_lora_rank)"
        assert cfg.dsa_index_heads > 0 and \
            cfg.dsa_index_query == "compressed", \
            "glm5's indexer reads the compressed query"
        assert cfg.moe_score_function == "sigmoid" and cfg.moe_choice_bias, \
            "glm5 routes by sigmoid scores with a choice bias"
        assert cfg.moe_shared_experts > 0, "glm5 has a shared expert"
        assert cfg.sliding_window_size is None
        super().__init__(cfg)


def glm5_config(size: str = "744B-A40B", **overrides) -> TransformerConfig:
    shapes = {
        # one dense layer; an indexer head of which half rotates; a top-k
        # under the tests' contexts; a compressed query narrower than the
        # heads' total
        "tiny": dict(num_layers=3, hidden_size=128, num_attention_heads=4,
                     num_attention_heads_kv=4, kv_channels=16,
                     ffn_hidden_size=256, moe_ffn_hidden_size=64,
                     padded_vocab_size=512, num_experts=8, moe_top_k=3,
                     moe_first_dense_layers=1, kv_lora_rank=32,
                     q_lora_rank=48, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=24, dsa_index_heads=4,
                     dsa_index_head_dim=16, dsa_index_rope_dim=8,
                     dsa_topk=8, seq_length=256,
                     max_position_embeddings=512),
        "744B-A40B": dict(num_layers=78, hidden_size=6144,
                          num_attention_heads=64, num_attention_heads_kv=64,
                          kv_channels=64, ffn_hidden_size=12288,
                          moe_ffn_hidden_size=2048,
                          padded_vocab_size=154880, num_experts=256,
                          moe_top_k=8, moe_first_dense_layers=3,
                          kv_lora_rank=512, q_lora_rank=2048,
                          qk_nope_head_dim=192, qk_rope_head_dim=64,
                          v_head_dim=256, dsa_index_heads=32,
                          dsa_index_head_dim=128, dsa_index_rope_dim=64,
                          dsa_topk=2048),
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
        moe_routed_scale=2.5,
        moe_shared_experts=1,
        dsa_index_query="compressed",
        rope_theta=1e6,
        seq_length=32768,
        max_position_embeddings=202752,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
