"""Bytes and operations of learned sparse attention (an indexer chooses
each query's ``topk`` keys) that no implementation could avoid, from
what a launch's record says it worked on, and the least time a chip
could take for them.

``cfg`` is a configuration in the published config's keys
(``sa_config``: the indexer's head size and ``topk``; the attention's
heads as ``roofline.py`` reads them).

Decode (``decode_bytes``): every live key's indexer key must be read to
be scored (``indexer_head_dim`` values of 2 bytes: 128 B a key a
layer), and every selected key's keys and values must be read to be
attended (2 x kv heads x head size x 2 bytes: 2,048 B a key a layer).
``keys_live`` / ``keys_selected`` are the record's ``dsa_keys_live`` /
``dsa_keys_selected``, already summed over rows and layers.  Not
counted: the scores' round trip through memory, the choice itself, the
queries, the output: an implementation may fuse them away.

A prefill chunk (``prefill_cost``): query j of the chunk at position
``start + j`` attends ``min(start + j + 1, topk)`` keys, 4 x heads x
head size operations each (the products with K and with V); the chunk
reads at least the keys and values of as many tokens as its last query
selects.  This is the cost under the SELECTION, which is what no
implementation could avoid; the kernel of today attends densely under a
mask and does more, so its share reads low, never high.
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import roofline


def index_key_bytes(cfg) -> int:
    return int(cfg["sa_config"]["indexer_head_dim"]) * roofline.BYTES


def kv_bytes(cfg) -> int:
    """Keys and values of one position in one layer."""
    return (2 * int(cfg["num_key_value_heads"]) * roofline.head_dim(cfg)
            * roofline.BYTES)


def decode_bytes(cfg, keys_live: int, keys_selected: int) -> float:
    """Bytes of a decode launch: its rows' live indexer keys and selected
    keys and values, both already summed over the layers."""
    return (keys_live * index_key_bytes(cfg)
            + keys_selected * kv_bytes(cfg))


def prefill_cost(cfg, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one chunk's attention under the selection, all
    layers."""
    layers = int(cfg["num_hidden_layers"])
    topk = int(cfg["sa_config"]["topk"])
    nh = int(cfg["num_attention_heads"])
    attended = sum(min(start + j + 1, topk) for j in range(valid))
    flops = 4.0 * nh * roofline.head_dim(cfg) * attended * layers
    nbytes = min(start + valid, topk) * kv_bytes(cfg) * layers
    return flops, float(nbytes)


def decode_least_seconds(cfg, keys_live: int, keys_selected: int,
                         peaks: Dict[str, float]) -> float:
    return roofline.least_seconds(
        0.0, decode_bytes(cfg, keys_live, keys_selected), peaks)[0]


def prefill_least_seconds(cfg, start: int, valid: int,
                          peaks: Dict[str, float]) -> float:
    return roofline.least_seconds(*prefill_cost(cfg, start, valid),
                                  peaks)[0]
