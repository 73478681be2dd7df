"""Bytes the paged walks of one launch cannot avoid, from what the
launch's record says it worked on, and the least time a chip could take
for them.

``cfg`` is a configuration in the published config's keys
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``layer_types`` and ``num_hidden_layers`` as run).  A token holds, in each
attention layer, one key and one value of ``num_key_value_heads`` heads
of ``hidden / heads`` in bf16: 2 x 8 x 64 x 2 B = 2,048 B at LFM2's
widths, which is what the pool holds them at (two heads a 128-lane row,
``ops/paged_kv.py``; a last dimension of 64 would be laid out, and
fetched, at twice that).  A decode launch reads every live row's context
and its own token (``DispatchRecord.context_tokens + rows``), a prefill
launch its request's history and its chunk (``start + valid``), each
once a layer.  The two products of attention do 4 operations a (query,
key, head, column) against these bytes: at one query a row bandwidth
bounds them, and a chunk's are left out, as are whole pages fetched for a
partial one and the prefixes a chunk's q-blocks each walk again.  So the
share can only read low.
"""

from __future__ import annotations

from typing import Dict

from . import roofline


def token_bytes(cfg) -> int:
    """Keys and values of one token in one attention layer."""
    return (2 * int(cfg["num_key_value_heads"]) * roofline.head_dim(cfg)
            * roofline.BYTES)


def attention_layers(cfg) -> int:
    types = list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]
    return sum(t == "full_attention" for t in types)


def launch_tokens(record) -> int:
    """The tokens whose keys and values a launch's walks read."""
    if record.kind == "prefill":
        return int(record.start) + int(record.valid)
    return int(record.context_tokens) + int(record.rows)


def least_seconds(cfg, tokens: int, peaks: Dict[str, float]) -> float:
    return (float(tokens) * token_bytes(cfg) * attention_layers(cfg)
            / peaks["hbm_bytes_per_s"])
