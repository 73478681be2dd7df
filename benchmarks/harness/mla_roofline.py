"""Bytes and operations of latent attention (every query head attends one
latent and one rotary key a token) that no implementation could avoid,
from what a launch's record says it worked on, and the least time a chip
could take for them.

``cfg`` is a configuration in the published config's keys
(``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``num_attention_heads``).  The cache is bf16.

Decode (``decode_least_seconds``): a live key is one row of the pool,
``kv_lora_rank + qk_rope_head_dim`` values of 2 bytes (1,152 B a key a
layer), read once; in the absorbed form, which is the only one that
reads no more than that, every head multiplies its query with the row
and its probabilities with the row's latent: ``2 x heads x (2 x
kv_lora_rank + qk_rope_head_dim)`` operations a key (69,632).  The
larger of the two times.  ``keys_live`` is the record's
``mla_keys_live``, already summed over rows and layers.

A prefill chunk (``prefill_least_seconds``): the cheaper form's
products, the expanded one's, with the expansion itself left out: ``2 x
heads x (qk_nope_head_dim + qk_rope_head_dim + v_head_dim)`` operations
a (query, key) pair (20,480), and the context's latents read once.
``pairs`` is the record's ``mla_pairs`` (summed over the layers),
``context`` the tokens the chunk's last query sees.  A least time counts
only what no implementation could avoid, so a share reads low, never
over 100: the absorbed chunk of today does 3.4 times the operations.
"""

from __future__ import annotations

from typing import Dict

from . import roofline


def key_bytes(cfg) -> int:
    """One token's row in one layer as the mathematics needs it."""
    return ((int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]))
            * roofline.BYTES)


def absorbed_flops_per_key(cfg) -> int:
    return 2 * int(cfg["num_attention_heads"]) * (
        2 * int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]))


def expanded_flops_per_pair(cfg) -> int:
    return 2 * int(cfg["num_attention_heads"]) * (
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
        + int(cfg["v_head_dim"]))


def decode_least_seconds(cfg, keys_live: int,
                         peaks: Dict[str, float]) -> float:
    return roofline.least_seconds(
        float(keys_live) * absorbed_flops_per_key(cfg),
        float(keys_live) * key_bytes(cfg), peaks)[0]


def prefill_least_seconds(cfg, pairs: int, context: int, layers: int,
                          peaks: Dict[str, float]) -> float:
    return roofline.least_seconds(
        float(pairs) * expanded_flops_per_pair(cfg),
        float(context) * key_bytes(cfg) * layers, peaks)[0]
