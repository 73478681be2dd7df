"""Plain reference: the Mellum 2 forward pass (``model_type`` ``mellum``,
``JetBrains/Mellum2-12B-A2.5B-Instruct``'s ``config.json``).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no capacity; one sequence at a time, a block of queries at a
time against the whole sequence's keys, one expert at a time.  The
equations, for layer l with RMSNorm'd input u (eps 1e-6 before attention
and before the MLP, a residual around each, a final RMSNorm, an untied
head):

* attention: ``q = W_q u`` (32 heads of 128), ``k = W_k u``,
  ``v = W_v u`` (4 heads of 128; 8 query heads a key-value head), no
  bias, NO QK-norm (assumed: the config has no key for one); the rotary
  embedding by the layer's type; scores ``q.k / sqrt(128)``; softmax in
  float32; output ``W_o``.  ``layer_types[l]`` says which type:

  - ``sliding_attention``: plain rotary, pair i turns by
    ``p * theta^(-2i/128)``, theta 500000; the query at position p sees
    the keys j with ``p - 1024 < j <= p`` (``sliding_window`` 1024).
  - ``full_attention``: YaRN (``rope_parameters.full_attention``):
    ``theta'_i = (theta_i / 16)(1 - g_i) + theta_i g_i`` with ``g_i = 1 -
    clip((i - low) / (high - low), 0, 1)``, ``low = floor(c(32))``,
    ``high = ceil(c(1))``, ``c(b) = 128 ln(8192 / (2 pi b)) / (2 ln
    500000)`` clipped to [0, 127] (low 18, high 35; i counts the 64
    pairs); cos and sin are multiplied by ``attention_factor``
    1.2772588722239782; every key ``j <= p`` is seen.

  Rotate-half convention, as published: within a head column i pairs
  with column i + 64.
* MLP, every layer (``mlp_layer_types`` all ``sparse``): ``r = W_r u``
  (64 logits), ``p = softmax(r)``, the 8 largest, gates ``g = p_top /
  sum(p_top)`` (``norm_topk_prob`` true), ``out = sum_e g_e W_down,e
  (silu(W_gate,e u) * W_up,e u)`` at width 896.  No shared expert.
* The multi-token-prediction head the model card names is not built.

Departures from the published code, none of which changes a value: the
program under test rotates INTERLEAVED pairs, which is this model with
the columns of W_q and W_k relabelled within each head
(``mellum_from_program.py``); every expert runs over every token,
weighted by its gate (zero where the token did not choose it).

``faults`` (a set of names) turns this file into a FAULTY reference, for
the readings the probe's limits rest on and for the tests' controls:
``all_full`` (every layer attends every key, under its own rotary
variant), ``all_window`` (every layer within the window), ``plain_rope``
(plain rotary on the full layers too), ``no_attention_factor`` (YaRN's
frequencies without the factor on cos and sin), ``gates_as_they_are``
(the chosen gates not renormalised), ``float8`` (weights and each
layer's normed input rounded to e4m3, the nearest precision below the
stated bf16), ``bf16`` (the stated precision itself).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
QUERY_BLOCK = 256
VOCAB_BLOCK = 16384
FAULTS = ("all_full", "all_window", "plain_rope", "no_attention_factor",
          "gates_as_they_are", "float8", "bf16")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_frequencies(d: int, theta: float, rope: dict) -> jax.Array:
    """The d/2 pair frequencies of a ``rope_type`` ``yarn`` entry of
    ``rope_parameters``, written out."""
    i = jnp.arange(d // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * i / d)
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def c(b):
        return d * math.log(orig / (2 * math.pi * b)) / (2 * math.log(theta))

    low = max(math.floor(c(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rope["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    g = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (freq / factor) * (1.0 - g) + freq * g


def rotary(x, positions, rope: dict, faults=frozenset()):
    """x [s, heads, d], rotate-half: (x[i], x[i + d/2]) turns by
    ``positions * freq_i``, the frequencies and the factor on cos and sin
    by ``rope`` (one type's entry of ``rope_parameters``)."""
    d = x.shape[-1]
    theta = float(rope["rope_theta"])
    factor = 1.0
    if rope["rope_type"] == "yarn" and "plain_rope" not in faults:
        freq = yarn_frequencies(d, theta, rope)
        if "no_attention_factor" not in faults:
            factor = float(rope["attention_factor"])
    else:
        freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]    # [s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return (x * cos + jnp.concatenate([-b, a], axis=-1) * sin) * factor


def _rounded(x, faults):
    """The precision faults: x as the named precision holds it."""
    if "float8" in faults:
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if "bf16" in faults:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _hashable(rope: dict):
    return tuple(sorted(rope.items()))


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps",
                                             "rope", "faults"))
def attention_inputs(x, w, *, n_heads, n_kv, eps, rope, faults):
    """q [s, heads, d], k, v [s, kv heads, d] of one layer for one
    sequence x [s, h], rotated."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        w = {k: _rounded(v, faults) for k, v in w.items()}
        hn = _rounded(rms_norm(x, w["attention_norm"], eps), faults)
        d = w["wq"].shape[1] // n_heads
        pos = jnp.arange(s)
        q = rotary((hn @ w["wq"]).reshape(s, n_heads, d), pos, dict(rope),
                   faults)
        k = rotary((hn @ w["wk"]).reshape(s, n_kv, d), pos, dict(rope),
                   faults)
        v = (hn @ w["wv"]).reshape(s, n_kv, d)
        return q, k, v


@functools.partial(jax.jit, static_argnames=("window",))
def attend_block(q, k, v, first, *, window):
    """A block of queries [bq, heads, d] at positions ``first ..`` over
    the whole sequence's keys: the query at p sees ``j <= p`` and, with a
    window, ``p - window < j``.  Returns [bq, heads * d]."""
    with jax.default_matmul_precision(HIGHEST):
        bq, n_heads, d = q.shape
        T, n_kv, _ = k.shape
        p = (first + jnp.arange(bq))[:, None]
        j = jnp.arange(T)[None, :]
        seen = j <= p
        if window is not None:
            seen &= p - window < j
        rep = n_heads // n_kv
        kk = jnp.repeat(k, rep, axis=1)
        vv = jnp.repeat(v, rep, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, kk) / math.sqrt(d)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, vv).reshape(bq, n_heads * d)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "renormalise"))
def moe_gates(x, ffn_norm, gate, forced, *, eps, top_k, renormalise):
    """Normed input; for every token and expert the weight that expert
    gets (its softmax value over ALL experts, renormalised over the
    token's top_k, if it is among them, else zero); the router's margin
    (the last chosen expert's logit minus the first rejected one's); the
    experts chosen [s, top_k]; and how far below the last chosen
    expert's logit the lowest of them lies (0 where they are the
    router's own).  A row of ``forced`` [s, top_k] that is not negative
    is taken for the token's experts as it stands (the gates still this
    router's own values over them)."""
    with jax.default_matmul_precision(HIGHEST):
        hn = rms_norm(x, ffn_norm, eps)
        logits = hn @ gate                                  # [s, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(logits, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        idx = jnp.where(forced[:, :1] >= 0, forced, idx[:, :top_k])
        below = top[:, top_k - 1] - jnp.min(
            jnp.take_along_axis(logits, idx, axis=1), axis=1)
        chosen = jnp.take_along_axis(probs, idx, axis=1)
        if renormalise:
            chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        dense = jnp.zeros_like(logits)
        dense = dense.at[jnp.arange(x.shape[0])[:, None], idx].set(chosen)
        return hn, dense, margin, idx, jnp.maximum(below, 0.0)


@functools.partial(jax.jit, static_argnames=("faults",))
def expert_out(hn, gate_weight, w1, w2, w3, *, faults=frozenset()):
    """One expert over every token, weighted by its gate."""
    with jax.default_matmul_precision(HIGHEST):
        hn, w1, w2, w3 = (_rounded(a, faults) for a in (hn, w1, w2, w3))
        y = (jax.nn.silu(hn @ w1) * (hn @ w3)) @ w2
        return y * gate_weight[:, None]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_block(x, norm, output_rows, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm, eps) @ output_rows.T


def layer_kind(cfg: dict, i: int, faults=frozenset()):
    """(window or None, the rotary entry) of layer i as ``faults`` leave
    them: the mask by ``layer_types``, the rotary variant by the same."""
    kind = cfg["layer_types"][i]
    rope = cfg["rope_parameters"][kind]
    window = (int(cfg["sliding_window"]) if kind == "sliding_attention"
              else None)
    if "all_full" in faults:
        window = None
    if "all_window" in faults:
        window = int(cfg["sliding_window"])
    return window, rope


def attention_out(x, w, cfg, i, faults=frozenset()):
    """x [s, h] -> what layer i's attention adds to the stream [s, h] (a
    block of queries at a time)."""
    window, rope = layer_kind(cfg, i, faults)
    precision = frozenset(faults) & {"float8", "bf16"}
    q, k, v = attention_inputs(
        x, w, n_heads=int(cfg["num_attention_heads"]),
        n_kv=int(cfg["num_key_value_heads"]),
        eps=float(cfg["rms_norm_eps"]), rope=_hashable(rope),
        faults=frozenset(faults) - {"all_full", "all_window",
                                    "gates_as_they_are"})
    s = x.shape[0]
    out = [attend_block(q[first:first + QUERY_BLOCK], k, v, first,
                        window=window)
           for first in range(0, s, QUERY_BLOCK)]
    with jax.default_matmul_precision(HIGHEST):
        return jnp.concatenate(out) @ _rounded(w["wo"], precision)


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None, rows=None, faults=frozenset(),
                   routing: list = None, forced: dict = None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32), or [len(rows), vocab]
    at the positions ``rows``.  With a list for ``router_margins`` each
    layer appends its margins [s].  With a list for ``routing`` each
    layer appends (the experts chosen [s, top_k], how far below its own
    last choice the lowest of them lies [s]); ``forced`` maps a layer's
    index to {position: experts}: the experts that token is given there,
    whatever this router would choose (for reading what a program's own
    close choices explain).  ``turned`` is the probe's other way of
    saying so and is not implemented here."""
    if turned:
        raise NotImplementedError("give the experts (forced), not a turn")
    tokens = np.asarray(tokens, np.int32)
    s = len(tokens)
    faults = frozenset(faults)
    assert faults <= set(FAULTS), faults
    x = weights.embedding_rows(tokens)
    eps = float(cfg["rms_norm_eps"])
    top_k = int(cfg["num_experts_per_tok"])
    for i in range(int(cfg["num_hidden_layers"])):
        assert cfg["mlp_layer_types"][i] == "sparse"
        w = weights.layer(i)
        x = x + attention_out(x, w, cfg, i, faults)
        given = np.full((s, top_k), -1, np.int32)
        for t, experts in (forced or {}).get(i, {}).items():
            given[t] = experts
        hn, dense, margin, chose, below = moe_gates(
            x, w["ffn_norm"], w["gate"], jnp.asarray(given), eps=eps,
            top_k=top_k,
            renormalise=(bool(cfg["norm_topk_prob"])
                         and "gates_as_they_are" not in faults))
        if router_margins is not None:
            router_margins.append(margin)
        if routing is not None:
            routing.append((np.asarray(chose), np.asarray(below)))
        y = jnp.zeros_like(x)
        # as many experts as the router has logits (a rehearsal's program
        # has fewer than the file's num_experts)
        for e in range(w["gate"].shape[1]):
            ew = weights.expert(i, e)
            y = y + expert_out(hn, dense[:, e], ew["w1"], ew["w2"], ew["w3"],
                               faults=faults & {"float8", "bf16"})
        x = x + y
        del w
    if rows is not None:
        x = x[jnp.asarray(np.asarray(rows, np.int32))]
    norm = weights.final_norm()
    vocab = int(cfg["vocab_size"])
    return jnp.concatenate(
        [head_block(x, norm, weights.output_rows(v0, min(v0 + VOCAB_BLOCK,
                                                         vocab)), eps=eps)
         for v0 in range(0, vocab, VOCAB_BLOCK)], axis=-1)


def position_losses(logits, labels) -> jax.Array:
    """Cross entropy at every position [s] (float32)."""
    labels = jnp.asarray(np.asarray(labels, np.int32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def cross_entropy(logits, labels) -> jax.Array:
    """Summed cross entropy over positions (float32)."""
    return jnp.sum(position_losses(logits, labels))
