"""Operations and bytes a launch of a LOOPED stack cannot avoid, from
what the launch's record says it worked on, and the least time a chip
could take for them.

``cfg`` is a configuration in the published config's keys (those of
``harness/roofline.py`` and ``total_ut_steps``, the passes;
``num_hidden_layers`` as run).  ``harness/roofline.py`` counts a layer
once; here a token passes ``total_ut_steps x num_hidden_layers`` layer
applications, and holds as many cache planes (a plane: the keys and
values of one layer in one pass, ``2 x num_key_value_heads x head_dim x
2 B``: 8,192 B at the published widths, 393,216 B a token over twelve
layers and four passes).

* The layers' weights are read ONCE A PASS: 1.2 GB of them do not stay
  in VMEM between passes, and pass t + 1 cannot start before pass t ends
  (its input is pass t's normed output).  The head is read once a
  launch that computes logits.
* A decode launch reads every live row's context in every plane and
  writes its own token there (``context_tokens + rows`` tokens of
  393,216 B); a chunk its history and itself (``start + valid``).
* Operations: 2 a weight a token a pass, and the two products of
  attention, 4 a (query, key, head, column) a plane.

Only what no implementation could avoid is counted (norms, rotary,
softmax, the sampler, whole pages fetched for a partial one, a chunk's
q-blocks each walking their prefix and the pool's copy a chunk is lent
are not), so a share reads low and never over 100, whatever implements
the launch.
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import roofline


def passes(cfg) -> int:
    return int(cfg.get("total_ut_steps") or 1)


def plane_bytes(cfg) -> int:
    """Keys and values of one token in one layer in one pass."""
    return (2 * int(cfg["num_key_value_heads"]) * roofline.head_dim(cfg)
            * roofline.BYTES)


def planes(cfg) -> int:
    """Cache planes a token holds: layers x passes."""
    return int(cfg["num_hidden_layers"]) * passes(cfg)


def token_bytes(cfg) -> int:
    return plane_bytes(cfg) * planes(cfg)


def layer_runs(cfg) -> int:
    """Layer applications a token: the same layers x passes."""
    return planes(cfg)


def _weights_bytes(cfg, head: bool) -> float:
    return roofline.BYTES * (
        layer_runs(cfg) * roofline.layer_params(cfg)
        + (roofline.head_params(cfg) if head else 0))


def _pair_flops(cfg) -> float:
    """The two products of attention over one (query, key) in every
    plane."""
    return 4.0 * int(cfg["num_attention_heads"]) * roofline.head_dim(
        cfg) * planes(cfg)


def decode_cost(cfg, rows: int, context_tokens: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of a decode launch of ``rows`` live rows whose
    caches hold ``context_tokens`` tokens together."""
    read = int(context_tokens) + int(rows)
    flops = (2.0 * rows * (layer_runs(cfg) * roofline.active_layer_params(cfg)
                           + roofline.head_params(cfg))
             + _pair_flops(cfg) * read)
    return flops, _weights_bytes(cfg, True) + float(read) * token_bytes(cfg)


def prefill_cost(cfg, start: int, valid: int, head_rows: int = 0
                 ) -> Tuple[float, float]:
    """(FLOPs, bytes) of a chunk of ``valid`` tokens at ``start``; the
    head for ``head_rows`` rows (1 for a request's last chunk)."""
    seen = valid * start + valid * (valid + 1) // 2
    flops = (2.0 * valid * layer_runs(cfg) * roofline.active_layer_params(cfg)
             + 2.0 * head_rows * roofline.head_params(cfg)
             + _pair_flops(cfg) * seen)
    return flops, (_weights_bytes(cfg, bool(head_rows))
                   + float(start + valid) * token_bytes(cfg))


def walk_bytes(cfg, tokens: int) -> float:
    """What the paged walks of a launch read: ``tokens`` tokens (a decode
    launch's ``context_tokens + rows``, a chunk's ``start + valid``) in
    every plane."""
    return float(tokens) * token_bytes(cfg)


def launch_tokens(record) -> int:
    if record.kind == "prefill":
        return int(record.start) + int(record.valid)
    return int(record.context_tokens) + int(record.rows)


def launch_least_seconds(cfg, record, peaks: Dict[str, float]) -> float:
    """The least seconds of one launch by its record."""
    if record.kind == "prefill":
        cost = prefill_cost(cfg, int(record.start), int(record.valid),
                            int(getattr(record, "prefill_head_rows", 0)))
    else:
        cost = decode_cost(cfg, int(record.rows), int(record.context_tokens))
    return roofline.least_seconds(*cost, peaks)[0]


def walk_least_seconds(cfg, record, peaks: Dict[str, float]) -> float:
    return walk_bytes(cfg, launch_tokens(record)) / peaks["hbm_bytes_per_s"]
