"""Brumby-14B-Base (Manifest AI, ``model_type`` ``brumby``): the
assert-the-flags wrapper that ``granite.py`` and ``lfm2.py`` are.

What the family is, as data of ``TransformerConfig`` (nothing in the
engine, the scheduler or the cache names it):

* a Qwen3-14B-shaped trunk (the model was retrained from those weights):
  40 query heads over 8 key-value heads of 128, each head's 128 values
  RMSNorm'd by themselves (``qk_norm_per_head``), rotated at theta 1e6,
  no bias anywhere, RMSNorm eps 1e-6, a dense SwiGLU MLP of 17,408, an
  untied head over 151,936 under the stack's final norm;
* EVERY layer's mixer a power retention of degree 2 (``layer_types``
  ``('retention',)``; ``models/retention.py``): attention's projections
  and one more, ``gate`` ``[hidden, 8]`` (a log-gate a key-value head a
  token), over a recurrent state ``S`` and its normaliser ``z`` a
  key-value head and NO key or value kept, so the model's pool has no
  page: a request holds one slot of the state group
  (``ops/paged_kv.py``), 34.3 MB a layer, whatever its length.
"""

from __future__ import annotations

from megatron_llm_tpu.config import PositionEmbeddingType, TransformerConfig
from megatron_llm_tpu.models.gpt import GPTModel


class BrumbyModel(GPTModel):
    def __init__(self, cfg: TransformerConfig):
        assert cfg.position_embedding_type == PositionEmbeddingType.rotary
        assert cfg.glu_activation == "swiglu"
        assert cfg.normalization == "rmsnorm"
        assert not cfg.add_bias_linear and not cfg.add_qkv_bias
        assert not cfg.tie_embed_logits, "brumby's head is its own"
        assert cfg.layer_types == ("retention",), \
            "brumby's every layer is a power retention (layer_types)"
        assert cfg.qk_norm_per_head, "brumby norms each query and key head"
        assert cfg.num_experts <= 1, "brumby is dense"
        super().__init__(cfg)


def brumby_config(size: str = "14b", **overrides) -> TransformerConfig:
    shapes = {
        # grouped queries kept: 4 query heads over 2 key-value heads of
        # 16, so phi has 9 rotations of 16
        "tiny": dict(num_layers=2, hidden_size=64, num_attention_heads=4,
                     num_attention_heads_kv=2, kv_channels=16,
                     ffn_hidden_size=128, padded_vocab_size=256,
                     seq_length=256, max_position_embeddings=1024),
        "14b": dict(num_layers=40, hidden_size=5120, num_attention_heads=40,
                    num_attention_heads_kv=8, kv_channels=128,
                    ffn_hidden_size=17408, padded_vocab_size=151936),
    }
    base = dict(
        position_embedding_type=PositionEmbeddingType.rotary,
        glu_activation="swiglu",
        normalization="rmsnorm",
        layernorm_epsilon=1e-6,
        add_bias_linear=False,
        tie_embed_logits=False,
        qk_norm_per_head=True,
        layer_types=("retention",),
        rope_theta=1000000.0,
        seq_length=32768,
        max_position_embeddings=32768,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    base.update(shapes[size])
    base.update(overrides)
    return TransformerConfig(**base)
