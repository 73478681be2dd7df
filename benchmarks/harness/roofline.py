"""Operations and bytes the algorithm needs, from shapes alone, and the
least time a chip could take for them.

``cfg`` is a configuration in the published config's keys
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``intermediate_size``, ``vocab_size``, ``num_hidden_layers`` as run,
``sliding_window``, and for sparse experts ``num_local_experts`` /
``num_experts_per_tok``).  Weights and the KV cache are bf16 (2 bytes).

Counted: matrix multiplications (2 FLOPs per multiply-add) and the two
attention products.  Not counted: norms, rotary, softmax, routing,
sampling — so a share of the roofline computed from these is a little
low, never high.  Bytes: every weight the step must touch read once, the
keys and values it must read, and those it writes; activations are left
out (they are small beside the weights at these batch sizes).
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

BYTES = 2   # bf16


def head_dim(cfg) -> int:
    return int(cfg.get("head_dim") or
               cfg["hidden_size"] // cfg["num_attention_heads"])


def attention_params(cfg) -> int:
    h, d = cfg["hidden_size"], head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nh * d + 2 * h * nkv * d + nh * d * h


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def experts(cfg) -> Tuple[int, int]:
    """(experts in a layer, experts a token uses); (1, 1) when dense."""
    e = int(cfg.get("num_local_experts") or 1)
    return e, (int(cfg["num_experts_per_tok"]) if e > 1 else 1)


def router_params(cfg) -> int:
    e, _ = experts(cfg)
    return cfg["hidden_size"] * e if e > 1 else 0


def layer_params(cfg) -> int:
    e, _ = experts(cfg)
    return attention_params(cfg) + router_params(cfg) + e * expert_params(cfg)


def active_layer_params(cfg) -> int:
    """Parameters one token multiplies with in a layer."""
    _, k = experts(cfg)
    return attention_params(cfg) + router_params(cfg) + k * expert_params(cfg)


def head_params(cfg) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg) -> int:
    norms = (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * head_params(cfg) + norms)      # embedding + output head


def experts_touched(cfg, tokens: int) -> float:
    """Experts of a layer whose weights ``tokens`` tokens need, expected
    under uniform routing (the least possible is the k of one token; with
    seeded random weights routing is close to uniform)."""
    e, k = experts(cfg)
    if e == 1:
        return 1.0
    return e * (1.0 - (1.0 - 1.0 / e) ** (tokens * k))


def kv_bytes_per_token(cfg) -> int:
    """Keys and values of one position, all layers."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg) * BYTES
            * cfg["num_hidden_layers"])


def _seen(cfg, context: int) -> int:
    w = cfg.get("sliding_window")
    return min(context, int(w)) if w else context


def _weight_bytes_touched(cfg, tokens: int) -> float:
    per_layer = (attention_params(cfg) + router_params(cfg)
                 + experts_touched(cfg, tokens) * expert_params(cfg))
    return cfg["num_hidden_layers"] * per_layer * BYTES


def decode_step_cost(cfg, context_tokens: Sequence[int]
                     ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step over rows whose caches hold
    ``context_tokens`` positions each (one new token a row)."""
    rows = len(context_tokens)
    layers, nh, d = cfg["num_hidden_layers"], cfg["num_attention_heads"], \
        head_dim(cfg)
    seen = sum(_seen(cfg, c + 1) for c in context_tokens)
    flops = (2.0 * rows * (layers * active_layer_params(cfg)
                           + head_params(cfg))
             + 4.0 * nh * d * layers * seen)
    nbytes = (_weight_bytes_touched(cfg, rows) + head_params(cfg) * BYTES
              + (seen + rows) * kv_bytes_per_token(cfg))
    return flops, nbytes


def prefill_chunk_cost(cfg, start: int, valid: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk of ``valid`` tokens appended at
    position ``start``.  The output head is left out: only a prompt's
    last position needs it."""
    layers, nh, d = cfg["num_hidden_layers"], cfg["num_attention_heads"], \
        head_dim(cfg)
    seen = sum(_seen(cfg, start + i + 1) for i in range(valid))
    flops = (2.0 * valid * layers * active_layer_params(cfg)
             + 4.0 * nh * d * layers * seen)
    nbytes = (_weight_bytes_touched(cfg, valid)
              + (_seen(cfg, start + valid) + valid) * kv_bytes_per_token(cfg))
    return flops, nbytes


def train_flops_per_token(cfg, seq_length: int) -> float:
    """Forward and backward (3 x forward), no recomputation, causal
    attention over a sequence of ``seq_length`` (mean keys seen per query
    under the window)."""
    layers, nh, d = cfg["num_hidden_layers"], cfg["num_attention_heads"], \
        head_dim(cfg)
    mean_seen = sum(_seen(cfg, i + 1) for i in range(seq_length)) / seq_length
    forward = (2.0 * (layers * active_layer_params(cfg) + head_params(cfg))
               + 4.0 * nh * d * layers * mean_seen)
    return 3.0 * forward


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, float]
                  ) -> Tuple[float, str]:
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = nbytes / peaks["hbm_bytes_per_s"]
    return ((by_compute, "compute") if by_compute >= by_memory
            else (by_memory, "bandwidth"))
