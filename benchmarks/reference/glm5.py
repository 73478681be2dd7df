"""Plain reference: the GLM-5 forward pass (``model_type``
``glm_moe_dsa``, ``zai-org/GLM-5``'s ``config.json``).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching; ``jax.lax.top_k`` for the selection; one sequence at a time, a
block of queries at a time against the whole sequence's keys, one expert
at a time.  Latent attention in its EXPANDED form only: every token's
keys and values are made from its latent, which is how the model is
published; the program's engine attends a decode step in the absorbed
form and selects through its own exact choice (``ops/dsa.py::choose``),
so this file is independent of it.  The equations, for a layer with
RMSNorm'd input u (eps 1e-5 before attention and before the MLP, a
residual around each, a final RMSNorm, an untied head), a token at
position t:

* query (``q_lora_rank`` 2048): ``c_q = RMSNorm_2048(W_qa u)`` (its own
  scale); ``q_h = W_qb,h c_q``, 64 heads of 256, a head's first 192
  ``q_nope``, its last 64 ``q_rope``.
* keys and values (``kv_lora_rank`` 512): ``[c' ; k'] = W_kva u`` (512 +
  64); ``c = RMSNorm_512(c')``; ``k_rope = k'`` is ONE head shared by all
  64; ``[k_nope,h ; v_h] = W_kvb,h c`` (192 + 256 a head).  ``q_rope``
  and ``k_rope`` turn at the token's position p over the pairs (2i,
  2i+1) by ``p * 1e6^(-2i/64)``.  ``rope_interleave`` true: the
  published code permutes each rope vector's pairs into halves and
  rotates halves; the same permutation on queries and keys leaves every
  product as it was, so rotating the interleaved pairs where they lie
  (as here, and as the program does) is the same function.
* indexer (``index_n_heads`` 32, ``index_head_dim`` 128, ``index_topk``
  2048): ``qI_j = W_iq,j c_q`` (the COMPRESSED query, not u);
  ``kI = LayerNorm_128(W_ik u)`` (scale and bias, ASSUMED as the
  published DSA code has it); the FIRST 64 of each indexer head's and of
  the key's 128 values turn (pairs (2i, 2i+1) by ``p * 1e6^(-2i/64)``,
  ``indexer_rope_interleave`` true; ASSUMED: the rotary part leads, as
  the published DSA code splits ``[pe ; nope]``), the other 64 pass;
  ``w_j = (W_iw u)_j * 32^-1/2 * 128^-1/2`` (ASSUMED, both factors from
  the published DSA code); ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])`` for ``s <= t``; ``S_t`` the positions of the 2,048 largest
  (equal scores: the earlier position first, ``jax.lax.top_k``'s rule,
  ASSUMED), every ``s <= t`` while there are no more than 2,048.  The
  published code's Hadamard rotation of ``qI`` and ``kI`` is one
  orthogonal matrix on both sides of a product, there for its FP8
  indexer cache; it changes no score and is left out.
* attention: ``s_h[t, s] = (q_nope,h . k_nope,h[s] + q_rope,h .
  k_rope[s]) / sqrt(256)`` over ``s in S_t``, softmax in float32, ``o_h =
  sum_s p v_h[s]``; the layer adds ``W_o [o_1 .. o_64]`` (16,384 ->
  6144).
* MLP, the first ``first_k_dense_replace`` layers: dense SwiGLU at
  ``intermediate_size`` 12288.
* MLP, the other layers: ``s = sigmoid(W_r u')`` (256 scores, float32);
  the 8 largest of ``s + b`` (b the layer's ``e_score_correction_bias``);
  ``n_group`` 1 and ``topk_group`` 1: no group is masked; gates ``g = s``
  at the chosen experts (WITHOUT b), ``g / (sum g + 1e-20)``
  (``norm_topk_prob``), times ``routed_scaling_factor`` 2.5; ``out =
  sum_e g_e W_down,e (silu(W_gate,e u') * W_up,e u')`` at width 2048,
  PLUS one shared SwiGLU MLP of width 2048 on every token, ungated.
* The multi-token-prediction layer (``num_nextn_predict_layers`` 1) is
  not built: next-token logits do not read it.

ONE CHIP'S SHARE of the experts: the router scores all its experts
(``gate``'s columns); of a token's chosen experts those HELD here
(``cfg['n_routed_experts']`` of them from ``cfg['experts_first']`` on)
are computed, the gates normalised over ALL the token's choices; what
the absent experts would add is left out, as the chip of the deployment
computes before the exchange (``held=None``; a test hands in every share
in turn and the shared expert once).

``router_margins``, ``routing`` and ``forced`` count SPARSE layers: entry
0 is the model's layer ``first_k_dense_replace``.  A margin, and how far
a given expert lies below the last chosen one, are in the units of the
CHOICE: score plus bias.

``faults`` (a set of names) turns this file into a FAULTY reference, for
the readings the probe's limits rest on and for the tests' controls:
``dense`` (every ``s <= t`` attended), ``topk_half`` (half the latents
selected: 1,024), ``unweighted`` (every ``w[t, j]`` equal),
``index_query_from_input`` (the indexer's queries read the layer's
normed input, its first ``q_lora_rank`` columns, in the compressed
query's place), ``no_query_norm`` (the norm on ``c_q`` left out),
``index_no_rope`` (nothing of the indexer turns), ``index_rope_whole``
(all 128 of an indexer head turn, pair i by ``p * 1e6^(-2i/128)``),
``no_latent_norm``, ``bias_in_gates`` (the gates the scores plus the
bias), ``bias_left_out`` (the choice over the scores alone),
``no_scale``, ``no_shared``, ``float8`` (weights and each layer's normed
inputs rounded to e4m3, the nearest precision below the stated bf16),
``bf16`` (the stated precision itself).
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
FAULTS = ("dense", "topk_half", "unweighted", "index_query_from_input",
          "no_query_norm", "index_no_rope", "index_rope_whole",
          "no_latent_norm", "bias_in_gates", "bias_left_out", "no_scale",
          "no_shared", "float8", "bf16")
PRECISION = frozenset({"float8", "bf16"})
ROUTER = frozenset({"bias_in_gates", "bias_left_out", "no_scale"})


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rotary(x, positions, theta: float):
    """x [s, heads, d]: the pairs (2i, 2i+1) turn by
    ``positions * theta^(-2i/d)``."""
    s, n, d = x.shape
    freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x.reshape(s, n, d // 2, 2)[..., 0], x.reshape(s, n, d // 2, 2)[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(s, n, d)


def _rounded(x, faults):
    """The precision faults: x as the named precision holds it."""
    if "float8" in faults:
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if "bf16" in faults:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "nope", "rope", "dv", "index_heads", "index_rope", "eps",
    "theta", "faults"))
def attention_inputs(x, w, *, n_heads, nope, rope, dv, index_heads,
                     index_rope, eps, theta, faults):
    """Everything of a layer's attention that is per token, for one
    sequence x [s, h]: q, k [s, heads, nope + rope] and v [s, heads, dv],
    every token's keys and values expanded from its latent, and the
    indexer's queries [s, Hi, di], key [s, di] and weights [s, Hi]."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        w = {k: _rounded(v, faults) for k, v in w.items()}
        u = _rounded(rms_norm(x, w["attention_norm"], eps), faults)
        r = w["kv_norm"].shape[0]
        pos = jnp.arange(s)
        c_q = u @ w["w_qa"]
        if "no_query_norm" not in faults:
            c_q = rms_norm(c_q, w["q_norm"], eps)
        q = (c_q @ w["w_qb"]).reshape(s, n_heads, nope + rope)
        kva = u @ w["w_kva"]                                # [s, r + rope]
        c = kva[:, :r]
        if "no_latent_norm" not in faults:
            c = rms_norm(c, w["kv_norm"], eps)
        kvb = (c @ w["w_kvb"]).reshape(s, n_heads, nope + dv)
        k_rope = jnp.broadcast_to(kva[:, None, r:], (s, n_heads, rope))
        k = jnp.concatenate([kvb[..., :nope], k_rope], axis=-1)
        q = jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], pos, theta)], axis=-1)
        k = jnp.concatenate(
            [k[..., :nope], rotary(k[..., nope:], pos, theta)], axis=-1)
        # the indexer: its queries read the COMPRESSED query
        di = w["index_wk"].shape[1]
        from_ = (u[:, :w["index_wq"].shape[0]]
                 if "index_query_from_input" in faults else c_q)
        iq = (from_ @ w["index_wq"]).reshape(s, index_heads, di)
        ik = layer_norm(u @ w["index_wk"], w["index_k_norm"],
                        w["index_k_bias"], eps)[:, None, :]
        turn = (0 if "index_no_rope" in faults
                else di if "index_rope_whole" in faults else index_rope)
        if turn:
            iq, ik = (jnp.concatenate(
                [rotary(a[..., :turn], pos, theta), a[..., turn:]], axis=-1)
                for a in (iq, ik))
        iw = (u @ w["index_ww"]) * index_heads ** -0.5 * di ** -0.5
        if "unweighted" in faults:
            iw = jnp.full_like(iw, index_heads ** -0.5 * di ** -0.5)
        return q, k, kvb[..., nope:], iq, ik[:, 0, :], iw


@functools.partial(jax.jit, static_argnames=("scale", "topk", "dense"))
def attend_block(q, k, v, iq, ik, iw, first, *, scale, topk, dense):
    """A block of queries [bq, ...] at positions ``first ..`` over the
    whole sequence's keys: the indexer's scores, the topk largest over
    ``s <= t``, softmax over the chosen keys only.  Returns [bq, heads *
    dv]."""
    with jax.default_matmul_precision(HIGHEST):
        bq = q.shape[0]
        T = k.shape[0]
        seen = (jnp.arange(T)[None, :]
                <= (first + jnp.arange(bq))[:, None])            # s <= t
        score = jnp.einsum(
            "qj,qjs->qs", iw,
            jax.nn.relu(jnp.einsum("qjd,sd->qjs", iq, ik)))
        score = jnp.where(seen, score, -jnp.inf)
        if dense:
            chosen = seen
        else:
            # lax.top_k puts the lower index first among equal scores.  A
            # query with fewer than topk keys behind it draws -inf entries
            # too: they are not seen, so not chosen
            _, idx = jax.lax.top_k(score, min(topk, T))
            chosen = jnp.zeros((bq, T), bool).at[
                jnp.arange(bq)[:, None], idx].set(True) & seen
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        scores = jnp.where(chosen[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v).reshape(bq, -1)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "renormalise",
                                             "scale", "faults"))
def moe_gates(x, ffn_norm, gate, bias, forced, *, eps, top_k, renormalise,
              scale, faults):
    """Normed input; for every token and expert of the ROUTER's the weight
    that expert gets (zero where the token did not choose it); the
    router's margin (the last chosen expert's CHOICE value, score plus
    bias, minus the first rejected one's); the experts chosen [s, top_k];
    and how far below the last chosen expert's choice value the lowest of
    them lies (0 where they are the router's own).  A row of ``forced``
    [s, top_k] that is not negative is taken for the token's experts as
    it stands (the gates still this router's own values over them)."""
    with jax.default_matmul_precision(HIGHEST):
        hn = rms_norm(x, ffn_norm, eps)
        logits = hn @ gate                                  # [s, R]
        scores = jax.nn.sigmoid(logits)
        choice = scores if "bias_left_out" in faults else scores + bias
        top, idx = jax.lax.top_k(choice, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        idx = jnp.where(forced[:, :1] >= 0, forced, idx[:, :top_k])
        below = top[:, top_k - 1] - jnp.min(
            jnp.take_along_axis(choice, idx, axis=1), axis=1)
        chosen = jnp.take_along_axis(
            scores + bias if "bias_in_gates" in faults else scores, idx,
            axis=1)
        if renormalise:
            chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                               + 1e-20)
        if "no_scale" not in faults:
            chosen = chosen * scale
        dense = jnp.zeros_like(logits)
        dense = dense.at[jnp.arange(x.shape[0])[:, None], idx].set(chosen)
        return hn, dense, margin, idx, jnp.maximum(below, 0.0)


@functools.partial(jax.jit, static_argnames=("faults",))
def expert_out(hn, gate_weight, w1, w2, w3, *, faults=frozenset()):
    """One expert (or, with a weight of ones, a dense or the shared MLP)
    over every token, weighted by its gate."""
    with jax.default_matmul_precision(HIGHEST):
        hn, w1, w2, w3 = (_rounded(a, faults) for a in (hn, w1, w2, w3))
        y = (jax.nn.silu(hn @ w1) * (hn @ w3)) @ w2
        return y * gate_weight[:, None]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_block(x, norm, output_rows, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm, eps) @ output_rows.T


def attention_out(x, w, cfg, faults=frozenset()):
    """x [s, h] -> what the layer's attention adds to the stream [s, h]
    (a block of queries at a time)."""
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    topk = int(cfg["index_topk"])
    if "topk_half" in faults:
        topk = max(1, topk // 2)
    names = ("attention_norm", "w_qa", "q_norm", "w_qb", "w_kva", "kv_norm",
             "w_kvb", "index_wq", "index_wk", "index_k_norm", "index_k_bias",
             "index_ww")
    q, k, v, iq, ik, iw = attention_inputs(
        x, {n: w[n] for n in names},
        n_heads=int(cfg["num_attention_heads"]), nope=nope, rope=rope,
        dv=int(cfg["v_head_dim"]), index_heads=int(cfg["index_n_heads"]),
        index_rope=rope,
        eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        faults=faults - ROUTER - {"dense", "topk_half", "no_shared"})
    s = x.shape[0]
    out = []
    for first in range(0, s, QUERY_BLOCK):
        rows = slice(first, min(first + QUERY_BLOCK, s))
        out.append(attend_block(
            q[rows], k, v, iq[rows], ik, iw[rows], first,
            scale=1.0 / math.sqrt(nope + rope), topk=topk,
            dense="dense" in faults))
    with jax.default_matmul_precision(HIGHEST):
        return jnp.concatenate(out) @ _rounded(w["wo"], faults & PRECISION)


def held_experts(cfg: dict, routed: int) -> range:
    """The router's experts this share of the layer computes."""
    first = int(cfg.get("experts_first", 0))
    return range(first, min(first + int(cfg["n_routed_experts"]), routed))


def moe_out(x, w, weights, cfg, i: int, forced_rows, faults, held=None,
            shared=True):
    """x [s, h] -> (what layer i's experts and shared expert give [s, h],
    margins, chosen, below).  ``held``: the router's experts computed
    (None: ``held_experts``); the shared expert once (``shared``)."""
    s = x.shape[0]
    top_k = int(cfg["num_experts_per_tok"])
    given = np.full((s, top_k), -1, np.int32)
    for t, experts in forced_rows.items():
        given[t] = experts
    hn, dense, margin, chose, below = moe_gates(
        x, w["ffn_norm"], w["gate"], w["choice_bias"], jnp.asarray(given),
        eps=float(cfg["rms_norm_eps"]), top_k=top_k,
        renormalise=bool(cfg["norm_topk_prob"]),
        scale=float(cfg["routed_scaling_factor"]), faults=faults & ROUTER)
    routed = w["gate"].shape[1]
    own = faults & PRECISION
    y = jnp.zeros_like(x)
    for e in (held_experts(cfg, routed) if held is None else held):
        ew = weights.expert(i, e)
        y = y + expert_out(hn, dense[:, e], ew["w1"], ew["w2"], ew["w3"],
                           faults=own)
    if shared and "no_shared" not in faults:
        y = y + expert_out(hn, jnp.ones((s,), jnp.float32), w["shared_w1"],
                           w["shared_w2"], w["shared_w3"], faults=own)
    return y, margin, chose, below


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None, rows=None, faults=frozenset(),
                   routing: list = None, forced: dict = None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32), or [len(rows), vocab]
    at the positions ``rows``.  With a list for ``router_margins`` each
    SPARSE layer appends its margins [s].  With a list for ``routing``
    each sparse layer appends (the experts chosen [s, top_k], how far
    below its own last choice the lowest of them lies [s]); ``forced``
    maps a sparse layer's index to {position: experts}: the experts that
    token is given there, whatever this router would choose.  ``turned``
    is the probe's other way of saying so and is not implemented here."""
    if turned:
        raise NotImplementedError("give the experts (forced), not a turn")
    tokens = np.asarray(tokens, np.int32)
    s = len(tokens)
    faults = frozenset(faults)
    assert faults <= set(FAULTS), faults
    x = weights.embedding_rows(tokens)
    eps = float(cfg["rms_norm_eps"])
    dense_layers = int(cfg["first_k_dense_replace"])
    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        x = x + attention_out(x, w, cfg, faults)
        sparse = i - dense_layers
        if sparse < 0:
            hn = rms_norm(x, w["ffn_norm"], eps)
            x = x + expert_out(hn, jnp.ones((s,), jnp.float32), w["w1"],
                               w["w2"], w["w3"], faults=faults & PRECISION)
            continue
        y, margin, chose, below = moe_out(
            x, w, weights, cfg, i, (forced or {}).get(sparse, {}), faults)
        if router_margins is not None:
            router_margins.append(margin)
        if routing is not None:
            routing.append((np.asarray(chose), np.asarray(below)))
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
