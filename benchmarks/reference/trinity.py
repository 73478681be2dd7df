"""Plain reference: the Trinity-Mini forward pass (``model_type``
``afmoe``, ``arcee-ai/Trinity-Mini``'s ``config.json`` and the published
``modeling_afmoe.py``).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching; one sequence at a time, a block of queries at a time against
the whole sequence's keys, one expert at a time.  The equations, RMSNorm
(eps 1e-5) everywhere, no bias anywhere, an untied head:

* ``x0 = E[ids] * sqrt(hidden_size)`` (``mup_enabled``);
* layer l of type ``layer_types[l]``, FOUR norms:
  ``x = x + N_post_attn(attention(N_in(x)))``, then
  ``x = x + N_post_mlp(mlp(N_pre_mlp(x)))``;
* attention over ``a = N_in(x)``: ``q = W_q a`` (32 heads of 128),
  ``k = W_k a``, ``v = W_v a`` (4 heads of 128; 8 query heads a
  key-value head), ``g = W_g a`` (32 x 128); each query and key head's
  128 values are RMSNorm'd by themselves under ONE scale of 128 for the
  queries and one for the keys; then

  - ``sliding_attention``: q and k turn by the plain rotary embedding
    (pair i by ``p * theta^(-2i/128)``, theta 10,000; rotate-half, as
    published: within a head column i pairs with column i + 64), and the
    query at p sees the keys j with ``p - sliding_window < j <= p``;
  - ``full_attention``: NOTHING rotates (the layer carries no
    positions), every key ``j <= p`` is seen;

  scores ``q.k / sqrt(128)``, softmax in float32; the heads' output
  TIMES ``sigmoid(g)``, then ``W_o``;
* MLP of the first ``num_dense_layers`` layers: SwiGLU at
  ``intermediate_size``;
* MLP of the others, over ``m = N_pre_mlp(x)``: ``s = sigmoid(W_r m)``
  (a score an expert), the ``num_experts_per_tok`` largest of ``s + b``
  (b the layer's ``expert_bias`` buffer: the CHOICE only), gates ``s``
  at the chosen experts ``/ (their sum + 1e-20)`` (``route_norm``) times
  ``route_scale``; ``sum_e g_e SwiGLU_e(m)`` at ``moe_intermediate_size``
  PLUS one shared SwiGLU expert of that width on every token, not
  weighted by the router.

THE SHARE.  The router scores as many experts as its matrix has columns;
the experts COMPUTED are ``experts_first .. experts_first + num_experts``
of them (``held_experts``: one chip's share of a layer whose experts
are spread over several; the gates stay normalised over ALL a token's
choices) and the shared expert always.  With every expert held this is
the published layer.

``router_margins``, ``routing`` and ``forced`` count SPARSE layers: entry
0 is the model's layer ``num_dense_layers`` (the engine's routing record
has a row a sparse layer).  A margin, and how far a given expert lies
below the last chosen one, are in the units of the CHOICE: score plus
bias.

Departures from the published code, none of which changes a value: every
held expert runs over every token, weighted by its gate (zero where the
token did not choose it); the program under test rotates INTERLEAVED
pairs, which is this model with the columns of W_q and W_k (and the
entries of the two head scales) relabelled within each head
(``trinity_from_program.py``).

``faults`` (a set of names) turns this file into a FAULTY reference, for
the readings the probe's limits rest on and for the tests' controls:
``no_gate`` (the heads' output not multiplied), ``full_rotates`` (the
full layers rotate too), ``no_output_norms`` (``x + f(norm(x))``, two
norms a layer), ``no_scale`` (gates not times ``route_scale``),
``bias_in_gates`` (the gates the scores plus the bias),
``no_multiplier`` (the embeddings as they lie), ``all_full`` (every
layer sees every key, under its own rotation), ``dense_layer_sparse``
(the LAST dense layer runs the first sparse layer's MLP in place of its
own), ``no_shared`` (the shared expert left out), ``no_qk_norm`` (the
heads not normed), ``float8`` (weights and each layer's normed inputs
rounded to e4m3, the nearest precision below the stated bf16), ``bf16``
(the stated precision itself).  An output norm hides any uniform scale
of a sublayer's output, so no fault of that form is named.
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
FAULTS = ("no_gate", "full_rotates", "no_output_norms", "no_scale",
          "bias_in_gates", "no_multiplier", "all_full",
          "dense_layer_sparse", "no_shared", "no_qk_norm", "float8", "bf16")
PRECISION = frozenset({"float8", "bf16"})


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, positions, theta: float):
    """x [s, heads, d], rotate-half: (x[i], x[i + d/2]) turns by
    ``positions * theta^(-2i/d)``."""
    d = x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]    # [s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-b, a], axis=-1) * sin


def _rounded(x, faults):
    """The precision faults: x as the named precision holds it
    (``reduce_precision``: the TPU's compiler drops an ``astype`` there
    and back)."""
    if "float8" in faults:
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    if "bf16" in faults:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps",
                                             "theta", "rotates", "faults"))
def attention_inputs(x, w, *, n_heads, n_kv, eps, theta, rotates, faults):
    """q [s, heads, d], k, v [s, kv heads, d] and the gate's
    pre-activations [s, heads * d] of one layer for one sequence
    x [s, h]; q and k normed a head and, where the layer ``rotates``,
    turned."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        w = {k: _rounded(v, faults) for k, v in w.items()}
        a = _rounded(rms_norm(x, w["attention_norm"], eps), faults)
        d = w["wq"].shape[1] // n_heads
        q = (a @ w["wq"]).reshape(s, n_heads, d)
        k = (a @ w["wk"]).reshape(s, n_kv, d)
        v = (a @ w["wv"]).reshape(s, n_kv, d)
        if "no_qk_norm" not in faults:
            q = rms_norm(q, w["q_norm"], eps)
            k = rms_norm(k, w["k_norm"], eps)
        if rotates:
            pos = jnp.arange(s)
            q, k = rotary(q, pos, theta), rotary(k, pos, theta)
        return q, k, v, a @ w["wg"]


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


@functools.partial(jax.jit, static_argnames=("top_k", "renormalise", "scale",
                                             "faults"))
def moe_gates(m, gate, bias, forced, *, top_k, renormalise, scale, faults):
    """For every token (its normed input m [s, h]) and router expert the
    weight that expert gets (zero where the token did not choose it); the
    router's margin (the last chosen expert's CHOICE value, score plus
    bias, minus the first rejected one's); the experts chosen
    [s, top_k]; and how far below the last chosen expert's choice value
    the lowest of them lies (0 where they are the router's own).  A row
    of ``forced`` [s, top_k] that is not negative is taken for the
    token's experts as it stands (the gates still this router's own
    values over them)."""
    with jax.default_matmul_precision(HIGHEST):
        scores = jax.nn.sigmoid(m @ gate)                   # [s, routed]
        choice = scores + bias
        top, idx = jax.lax.top_k(choice, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        idx = jnp.where(forced[:, :1] >= 0, forced, idx[:, :top_k])
        below = top[:, top_k - 1] - jnp.min(
            jnp.take_along_axis(choice, idx, axis=1), axis=1)
        chosen = jnp.take_along_axis(
            choice if "bias_in_gates" in faults else scores, idx, axis=1)
        if renormalise:
            chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                               + 1e-20)
        if "no_scale" not in faults:
            chosen = chosen * scale
        dense = jnp.zeros_like(scores)
        dense = dense.at[jnp.arange(m.shape[0])[:, None], idx].set(chosen)
        return dense, margin, idx, jnp.maximum(below, 0.0)


@functools.partial(jax.jit, static_argnames=("faults",))
def expert_out(m, gate_weight, w1, w2, w3, *, faults=frozenset()):
    """One expert (or, with a weight of ones, a dense or the shared MLP)
    over every token, weighted by its gate."""
    with jax.default_matmul_precision(HIGHEST):
        m, w1, w2, w3 = (_rounded(a, faults) for a in (m, w1, w2, w3))
        y = (jax.nn.silu(m @ w1) * (m @ w3)) @ w2
        return y * gate_weight[:, None]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_block(x, norm, output_rows, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm, eps) @ output_rows.T


def layer_kind(cfg: dict, i: int, faults=frozenset()):
    """(window or None, whether q and k rotate) of layer i as ``faults``
    leave them."""
    sliding = cfg["layer_types"][i] == "sliding_attention"
    window = int(cfg["sliding_window"]) if sliding else None
    if "all_full" in faults:
        window = None
    return window, sliding or "full_rotates" in faults


def attention_out(x, w, cfg, i, faults=frozenset()):
    """x [s, h] -> what layer i's attention gives [s, h] BEFORE the
    output norm (a block of queries at a time)."""
    window, rotates = layer_kind(cfg, i, faults)
    precision = faults & PRECISION
    names = ("attention_norm", "wq", "wk", "wv", "wg", "q_norm", "k_norm")
    q, k, v, g = attention_inputs(
        x, {n: w[n] for n in names},
        n_heads=int(cfg["num_attention_heads"]),
        n_kv=int(cfg["num_key_value_heads"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        rotates=rotates, faults=faults & (PRECISION | {"no_qk_norm"}))
    out = jnp.concatenate(
        [attend_block(q[first:first + QUERY_BLOCK], k, v, first,
                      window=window)
         for first in range(0, x.shape[0], QUERY_BLOCK)])
    with jax.default_matmul_precision(HIGHEST):
        if "no_gate" not in faults:
            out = out * jax.nn.sigmoid(g)
        return out @ _rounded(w["wo"], precision)


def held_experts(cfg: dict, routed: int) -> range:
    """The router's experts this share of the layer computes."""
    first = int(cfg.get("experts_first", 0))
    return range(first, min(first + int(cfg["num_experts"]), routed))


def moe_out(m, w, weights, cfg, i: int, forced_rows, faults, held=None):
    """The normed input m [s, h] -> (what layer i's experts and shared
    expert give [s, h] BEFORE the output norm, margins, chosen, below).
    ``held``: the router's experts computed (None: ``held_experts``); the
    shared expert once."""
    s = m.shape[0]
    top_k = int(cfg["num_experts_per_tok"])
    given = np.full((s, top_k), -1, np.int32)
    for t, experts in forced_rows.items():
        given[t] = experts
    precision = faults & PRECISION
    dense, margin, chose, below = moe_gates(
        m, w["gate"], w["choice_bias"], jnp.asarray(given), top_k=top_k,
        renormalise=bool(cfg["route_norm"]),
        scale=float(cfg["route_scale"]),
        faults=faults & {"bias_in_gates", "no_scale"})
    y = jnp.zeros_like(m)
    for e in (held_experts(cfg, w["gate"].shape[1]) if held is None
              else held):
        ew = weights.expert(i, e)
        y = y + expert_out(m, dense[:, e], ew["w1"], ew["w2"], ew["w3"],
                           faults=precision)
    if "no_shared" not in faults:
        y = y + expert_out(m, jnp.ones((s,), jnp.float32), w["shared_w1"],
                           w["shared_w2"], w["shared_w3"], faults=precision)
    return y, margin, chose, below


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None, rows=None, faults=frozenset(),
                   routing: list = None, forced: dict = None,
                   held=None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32), or [len(rows), vocab]
    at the positions ``rows``.  With a list for ``router_margins`` each
    SPARSE layer appends its margins [s].  With a list for ``routing``
    each sparse layer appends (the experts chosen [s, top_k], how far
    below its own last choice the lowest of them lies [s]); ``forced``
    maps a sparse layer's index to {position: experts}: the experts that
    token is given there, whatever this router would choose.  ``held``:
    the router's experts computed in every sparse layer (None: the share
    ``cfg`` states).  ``turned`` is the probe's other way of saying so
    and is not implemented here."""
    if turned:
        raise NotImplementedError("give the experts (forced), not a turn")
    tokens = np.asarray(tokens, np.int32)
    s = len(tokens)
    faults = frozenset(faults)
    assert faults <= set(FAULTS), faults
    precision = faults & PRECISION
    eps = float(cfg["rms_norm_eps"])
    dense_layers = int(cfg["num_dense_layers"])
    x = weights.embedding_rows(tokens)
    if bool(cfg.get("mup_enabled", True)) and "no_multiplier" not in faults:
        x = x * math.sqrt(float(cfg["hidden_size"]))
    ones = jnp.ones((s,), jnp.float32)

    def out_norm(y, scale):
        return y if "no_output_norms" in faults else rms_norm(y, scale, eps)

    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        x = x + out_norm(attention_out(x, w, cfg, i, faults),
                         w["attention_out_norm"])
        m = _rounded(rms_norm(x, w["ffn_norm"], eps), precision)
        sparse = i - dense_layers
        if sparse == -1 and "dense_layer_sparse" in faults:
            # the first sparse layer's MLP under this layer's norms
            w = {**weights.layer(dense_layers),
                 "mlp_out_norm": w["mlp_out_norm"]}
            i, sparse = dense_layers, None
        if sparse is not None and sparse < 0:
            y = expert_out(m, ones, w["w1"], w["w2"], w["w3"],
                           faults=precision)
        else:
            y, margin, chose, below = moe_out(
                m, w, weights, cfg, i,
                (forced or {}).get(sparse, {}) if sparse is not None else {},
                faults, held)
            if sparse is not None:
                if router_margins is not None:
                    router_margins.append(margin)
                if routing is not None:
                    routing.append((np.asarray(chose), np.asarray(below)))
        x = x + out_norm(y, w["mlp_out_norm"])
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
