"""The sizes the program really ran, in the published config's keys."""

from __future__ import annotations


def model_shape(mcfg) -> dict:
    shape = {
        "num_hidden_layers": int(mcfg.num_layers),
        "hidden_size": int(mcfg.hidden_size),
        "num_attention_heads": int(mcfg.num_attention_heads),
        "num_key_value_heads": int(mcfg.num_attention_heads_kv),
        "intermediate_size": int(mcfg.ffn_hidden_size),
        "vocab_size": int(mcfg.padded_vocab_size),
        "rms_norm_eps": float(mcfg.layernorm_epsilon),
        "rope_theta": float(mcfg.rope_theta),
        "sliding_window": (int(mcfg.sliding_window_size)
                           if mcfg.sliding_window_size else None),
    }
    if int(mcfg.num_experts or 0) > 1:
        shape["num_local_experts"] = int(mcfg.num_experts)
        shape["num_experts_per_tok"] = int(mcfg.moe_top_k)
    return shape


def differs_from_published(shape: dict, config: dict) -> list:
    """Keys whose value as run is not the configuration file's (the file
    holds the configuration as it is run: a reduced key holds the reduced
    value, the published one sits under ``published``)."""
    return [k for k, v in shape.items() if k in config and config[k] != v]
