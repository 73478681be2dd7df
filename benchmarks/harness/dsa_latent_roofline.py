"""Bytes and operations of the SELECTION OVER LATENTS (an indexer chooses
each query's ``index_topk`` positions and the query attends those rows
of a latent pool) that no implementation could avoid, from what a
launch's record says it worked on, and the least time a chip could take
for them.

``cfg`` is a configuration in the published config's keys
(``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``num_attention_heads``; the indexer's flat keys
``index_n_heads``, ``index_head_dim``, ``index_topk``).  The cache is
bf16.

Decode (``decode_least_seconds``): every live position's indexer key
must be read to be scored (``index_head_dim`` values of 2 bytes: 256 B a
key a layer), and every selected position's row must be read to be
attended (``kv_lora_rank + qk_rope_head_dim`` values: 1,152 B a layer);
in the absorbed form, which is the only one that reads a row once, every
head multiplies its query with the row and its probabilities with the
row's latent: ``2 x heads x (2 x kv_lora_rank + qk_rope_head_dim)``
operations a selected row (139,264).  The larger of the two times.
``keys_live`` / ``keys_selected`` are the record's ``dsa_keys_live`` /
``dsa_keys_selected``, already summed over rows and layers.  Not counted:
the scores' own products and their round trip through memory, the choice
itself, the queries, the output: an implementation may fuse them away or
make them cheaper.

A prefill chunk (``prefill_cost``): query j of the chunk at position
``start + j`` attends ``min(start + j + 1, index_topk)`` rows at the
EXPANDED form's products with the expansion left out, ``2 x heads x
(qk_nope_head_dim + qk_rope_head_dim + v_head_dim)`` operations each
(65,536), and scores all ``start + j + 1`` positions it sees at ``2 x
index_n_heads x index_head_dim`` each (8,192); the chunk reads at least
its context's indexer keys and the rows of as many tokens as its last
query selects.  This is the cost under the SELECTION, which is what no
implementation could avoid; a kernel that attends densely under a mask
does more, so its share reads low, never high.
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import roofline


def index_key_bytes(cfg) -> int:
    return int(cfg["index_head_dim"]) * roofline.BYTES


def row_bytes(cfg) -> int:
    """One token's latent row in one layer as the mathematics needs it."""
    return ((int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]))
            * roofline.BYTES)


def absorbed_flops_per_row(cfg) -> int:
    return 2 * int(cfg["num_attention_heads"]) * (
        2 * int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]))


def expanded_flops_per_pair(cfg) -> int:
    return 2 * int(cfg["num_attention_heads"]) * (
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
        + int(cfg["v_head_dim"]))


def score_flops_per_pair(cfg) -> int:
    return 2 * int(cfg["index_n_heads"]) * int(cfg["index_head_dim"])


def decode_cost(cfg, keys_live: int, keys_selected: int
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of a decode launch, both counts already summed over
    the rows and the layers."""
    return (float(keys_selected) * absorbed_flops_per_row(cfg),
            float(keys_live) * index_key_bytes(cfg)
            + float(keys_selected) * row_bytes(cfg))


def prefill_cost(cfg, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one chunk's scores and attention under the
    selection, all layers."""
    layers = int(cfg["num_hidden_layers"])
    topk = int(cfg["index_topk"])
    seen = valid * start + valid * (valid + 1) // 2
    attended = sum(min(start + j + 1, topk) for j in range(valid))
    flops = (attended * expanded_flops_per_pair(cfg)
             + seen * score_flops_per_pair(cfg)) * float(layers)
    nbytes = ((start + valid) * index_key_bytes(cfg)
              + min(start + valid, topk) * row_bytes(cfg)) * layers
    return flops, float(nbytes)


def decode_least_seconds(cfg, keys_live: int, keys_selected: int,
                         peaks: Dict[str, float]) -> float:
    return roofline.least_seconds(
        *decode_cost(cfg, keys_live, keys_selected), peaks)[0]


def prefill_least_seconds(cfg, start: int, valid: int,
                          peaks: Dict[str, float]) -> float:
    return roofline.least_seconds(*prefill_cost(cfg, start, valid),
                                  peaks)[0]
