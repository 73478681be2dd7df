"""Plain reference: the kanana-2-30b-a3b forward pass (``model_type``
``deepseek_v3``, ``kakaocorp/kanana-2-30b-a3b-instruct-2601``'s
``config.json``).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching; one sequence at a time, a block of queries at a time against
the whole sequence's keys, one expert at a time.  Latent attention in its
EXPANDED form only: every token's keys and values are made from its
latent, which is how the model is published; the program's engine
attends in the absorbed form, so this file is independent of it.  The
equations, for a layer with RMSNorm'd input u (eps 1e-6 before attention
and before the MLP, a residual around each, a final RMSNorm, an untied
head):

* attention (``kv_lora_rank`` 512, ``q_lora_rank`` null, 32 heads, no
  bias): ``q_h = W_q u``, 192 wide a head, its first 128 ``q_nope``, its
  last 64 ``q_rope``; ``[c' ; k'] = W_kva u`` (512 + 64);
  ``c = RMSNorm_512(c')`` (eps 1e-6, its own scale); ``q_rope`` and
  ``k_rope = k'`` turn at the token's position p over the pairs
  (2i, 2i+1) by ``p * 1e6^(-2i/64)``; ``k_rope`` is ONE head shared by
  all 32; ``[k_nope,h ; v_h] = W_kvb,h c`` (128 + 128 a head); scores
  ``(q_nope . k_nope + q_rope . k_rope) / sqrt(192)``, causal, softmax
  in float32; output ``W_o [o_1 .. o_32]``.
  ``rope_interleave`` true: the published code permutes each rope
  vector's pairs (2i, 2i+1) into halves (i, i+32) and rotates halves;
  the same permutation on queries and keys leaves every product as it
  was, so rotating the interleaved pairs where they lie (as here, and as
  the program does) is the same function.
* MLP, layer 0 (``first_k_dense_replace`` 1): dense SwiGLU at
  ``intermediate_size`` 6144.
* MLP, the other layers: ``s = sigmoid(W_r u)`` (128 scores); the 6
  largest of ``s + b`` (b the layer's ``e_score_correction_bias``);
  ``n_group`` 1 and ``topk_group`` 1: no group is masked; gates
  ``g = s`` at the chosen experts (WITHOUT b), ``g / (sum g + 1e-20)``
  (``norm_topk_prob``), times ``routed_scaling_factor`` 2.448;
  ``out = sum_e g_e W_down,e (silu(W_gate,e u) * W_up,e u)`` at width
  768, PLUS a shared SwiGLU MLP of width 2 x 768 on every token, ungated.
* No multi-token-prediction head is built.

``router_margins``, ``routing`` and ``forced`` count SPARSE layers: entry
0 is the model's layer ``first_k_dense_replace`` (the engine's routing
record has a row a sparse layer).  A margin, and how far a given expert
lies below the last chosen one, are in the units of the CHOICE: score
plus bias.

``faults`` (a set of names) turns this file into a FAULTY reference, for
the readings the probe's limits rest on and for the tests' controls:
``softmax_router`` (scores by a softmax over the experts),
``bias_left_out`` (the choice over the scores alone), ``bias_in_gates``
(the gates the scores plus the bias), ``no_scale`` (the gates not
scaled), ``no_shared`` (the shared MLP left out), ``dense_layer_sparse``
(layer 0 runs the first sparse layer's MLP in place of its dense one),
``no_latent_norm`` (the latent not normed), ``scale_sqrt_nope`` (scores
over sqrt(128)), ``rope_key_per_head`` (head h's rotary key is the
shared one with its columns rolled by 2h: a key of its own),
``rope_whole_head`` (the whole 192 of a query and a key head turn, pair
i by ``p * 1e6^(-2i/192)``), ``float8`` (weights and each layer's normed
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
FAULTS = ("softmax_router", "bias_left_out", "bias_in_gates", "no_scale",
          "no_shared", "dense_layer_sparse", "no_latent_norm",
          "scale_sqrt_nope", "rope_key_per_head", "rope_whole_head",
          "float8", "bf16")
PRECISION = frozenset({"float8", "bf16"})


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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


@functools.partial(jax.jit, static_argnames=("n_heads", "nope", "rope", "dv",
                                             "eps", "theta", "faults"))
def attention_inputs(x, w, *, n_heads, nope, rope, dv, eps, theta, faults):
    """q, k [s, heads, nope + rope] and v [s, heads, dv] of one layer for
    one sequence x [s, h]: every token's keys and values expanded from
    its latent."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        w = {k: _rounded(v, faults) for k, v in w.items()}
        hn = _rounded(rms_norm(x, w["attention_norm"], eps), faults)
        r = w["kv_norm"].shape[0]
        pos = jnp.arange(s)
        q = (hn @ w["wq"]).reshape(s, n_heads, nope + rope)
        kva = hn @ w["w_kva"]                               # [s, r + rope]
        c = kva[:, :r]
        if "no_latent_norm" not in faults:
            c = rms_norm(c, w["kv_norm"], eps)
        kvb = (c @ w["w_kvb"]).reshape(s, n_heads, nope + dv)
        k_rope = jnp.broadcast_to(kva[:, None, r:], (s, n_heads, rope))
        if "rope_key_per_head" in faults:
            k_rope = jnp.stack([jnp.roll(kva[:, r:], 2 * h, axis=-1)
                                for h in range(n_heads)], axis=1)
        k = jnp.concatenate([kvb[..., :nope], k_rope], axis=-1)
        if "rope_whole_head" in faults:
            q, k = rotary(q, pos, theta), rotary(k, pos, theta)
        else:
            q = jnp.concatenate(
                [q[..., :nope], rotary(q[..., nope:], pos, theta)], axis=-1)
            k = jnp.concatenate(
                [k[..., :nope], rotary(k[..., nope:], pos, theta)], axis=-1)
        return q, k, kvb[..., nope:]


@functools.partial(jax.jit, static_argnames=("scale",))
def attend_block(q, k, v, first, *, scale):
    """A block of queries [bq, heads, d] at positions ``first ..`` over
    the whole sequence's keys, causal.  Returns [bq, heads * dv]."""
    with jax.default_matmul_precision(HIGHEST):
        bq, n_heads, _ = q.shape
        seen = (jnp.arange(k.shape[0])[None, :]
                <= (first + jnp.arange(bq))[:, None])
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        scores = jnp.where(seen[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v).reshape(bq, -1)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "renormalise",
                                             "scale", "faults"))
def moe_gates(x, ffn_norm, gate, bias, forced, *, eps, top_k, renormalise,
              scale, faults):
    """Normed input; for every token and expert the weight that expert
    gets (zero where the token did not choose it); the router's margin
    (the last chosen expert's CHOICE value, score plus bias, minus the
    first rejected one's); the experts chosen [s, top_k]; and how far
    below the last chosen expert's choice value the lowest of them lies
    (0 where they are the router's own).  A row of ``forced``
    [s, top_k] that is not negative is taken for the token's experts as
    it stands (the gates still this router's own values over them)."""
    with jax.default_matmul_precision(HIGHEST):
        hn = rms_norm(x, ffn_norm, eps)
        logits = hn @ gate                                  # [s, E]
        scores = (jax.nn.softmax(logits, axis=-1)
                  if "softmax_router" in faults else jax.nn.sigmoid(logits))
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
    names = ("attention_norm", "wq", "w_kva", "kv_norm", "w_kvb")
    q, k, v = attention_inputs(
        x, {n: w[n] for n in names},
        n_heads=int(cfg["num_attention_heads"]), nope=nope, rope=rope,
        dv=int(cfg["v_head_dim"]), eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]),
        faults=faults & (PRECISION | {"no_latent_norm", "rope_key_per_head",
                                      "rope_whole_head"}))
    scale = 1.0 / math.sqrt(nope if "scale_sqrt_nope" in faults
                            else nope + rope)
    out = [attend_block(q[first:first + QUERY_BLOCK], k, v, first,
                        scale=scale)
           for first in range(0, x.shape[0], QUERY_BLOCK)]
    with jax.default_matmul_precision(HIGHEST):
        return jnp.concatenate(out) @ _rounded(w["wo"], faults & PRECISION)


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
    precision = faults & PRECISION
    x = weights.embedding_rows(tokens)
    eps = float(cfg["rms_norm_eps"])
    top_k = int(cfg["num_experts_per_tok"])
    dense_layers = int(cfg["first_k_dense_replace"])
    ones = jnp.ones((s,), jnp.float32)
    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        x = x + attention_out(x, w, cfg, faults)
        sparse = i - dense_layers
        if sparse < 0 and "dense_layer_sparse" in faults:
            # the first sparse layer's MLP under this layer's norm
            w = {**weights.layer(dense_layers), "ffn_norm": w["ffn_norm"]}
            i, sparse = dense_layers, None
        if sparse is not None and sparse < 0:
            hn = rms_norm(x, w["ffn_norm"], eps)
            x = x + expert_out(hn, ones, w["w1"], w["w2"], w["w3"],
                               faults=precision)
            continue
        given = np.full((s, top_k), -1, np.int32)
        for t, experts in (forced or {}).get(sparse, {}).items():
            given[t] = experts
        hn, dense, margin, chose, below = moe_gates(
            x, w["ffn_norm"], w["gate"], w["choice_bias"],
            jnp.asarray(given), eps=eps, top_k=top_k,
            renormalise=bool(cfg["norm_topk_prob"]),
            scale=float(cfg["routed_scaling_factor"]),
            faults=faults - PRECISION)
        if sparse is not None:
            if router_margins is not None:
                router_margins.append(margin)
            if routing is not None:
                routing.append((np.asarray(chose), np.asarray(below)))
        y = jnp.zeros_like(x)
        # as many experts as the router has scores (a rehearsal's program
        # has fewer than the file's n_routed_experts)
        for e in range(w["gate"].shape[1]):
            ew = weights.expert(i, e)
            y = y + expert_out(hn, dense[:, e], ew["w1"], ew["w2"], ew["w3"],
                               faults=precision)
        if "no_shared" not in faults:
            y = y + expert_out(hn, ones, w["shared_w1"], w["shared_w2"],
                               w["shared_w3"], faults=precision)
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
