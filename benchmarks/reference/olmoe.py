"""Plain reference: the OLMoE-1B-7B forward pass.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no capacity.  It follows the published model (``model_type``
``olmoe``: the modelling code that goes with
``allenai/OLMoE-1B-7B-0125-Instruct``'s ``config.json``), one sequence
at a time:

* RMSNorm:  x * rsqrt(mean(x^2) + eps) * w
* attention: ``h = x + Wo attn(rope(q), rope(k), v)`` with
  ``q = RMSNorm_q(Wq n1(x))``, ``k = RMSNorm_k(Wk n1(x))``,
  ``v = Wv n1(x)``, no bias, no clipping (``clip_qkv`` null).  The two
  norms have learned scales of the WHOLE projection's width and take
  their mean square over the whole projection, before the split into
  heads and before the rotary embedding.  Rotary embedding in the
  rotate-half convention: within a head, column i pairs with column
  i + d/2 and the pair turns by position * theta^(-2i/d).  As many
  key-value heads as the configuration says (here as many as query
  heads); causal mask, no window.
* experts: ``y = h + sum over the k chosen e of p_e W2_e(silu(W1_e n2(h))
  * W3_e n2(h))`` where ``p = softmax(Wg n2(h))`` over ALL experts and
  the k largest ``p_e`` are used AS THEY ARE (``norm_topk_prob`` false:
  they sum to less than 1).  No shared expert.  No token is ever dropped.
* logits = n_f(y_L) Wout^T (untied head); the loss is the mean cross
  entropy.

Departures from the published code, none of which changes a value:

* The program under test rotates INTERLEAVED pairs (columns 2i, 2i+1 of
  a head).  That is this model with the columns of Wq and Wk, and the
  entries of the two QK-norm scales, relabelled by one fixed permutation
  within each head: the mean square over the whole projection and every
  query-key product are sums over those columns and do not see their
  order.  The relabelling lives in the weights adapter
  (``olmoe_from_program.py``); this file is rotate-half throughout.
* Every expert runs over every token and is weighted by its gate, zero
  where the token did not choose it: the same sum as gathering each
  expert's tokens, exact and cheap at a probe's few hundred tokens.
* ``turned``: where the program, which rounds activations to bf16, seats
  the first rejected expert (the ninth) in the last chosen one's (the
  eighth's) place, the probe asks for the same choice here at that
  position and layer; the ninth then carries its own softmax value.

Weights come one layer at a time (``weights.layer(i)``) and one expert
at a time (``weights.expert(i, e)``), so that a float32 copy of one
expert only is on the chip beside the program's own bf16 weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, positions, theta):
    """x [s, heads, d], rotate-half: (x[i], x[i + d/2]) turns by
    positions * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]    # [s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-b, a], axis=-1) * sin


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta",
                                             "eps"))
def attention_block(x, w, *, n_heads, n_kv, theta, eps):
    """x [s, h] -> x + attention(norm(x)) for one sequence."""
    with jax.default_matmul_precision(HIGHEST):
        s, _ = x.shape
        d = w["wq"].shape[1] // n_heads
        hn = rms_norm(x, w["attention_norm"], eps)
        pos = jnp.arange(s)
        # QK-norm over the whole projection, before heads and rotary
        q = rms_norm(hn @ w["wq"], w["q_norm"], eps)
        k = rms_norm(hn @ w["wk"], w["k_norm"], eps)
        q = rotary(q.reshape(s, n_heads, d), pos, theta)
        k = rotary(k.reshape(s, n_kv, d), pos, theta)
        v = (hn @ w["wv"]).reshape(s, n_kv, d)
        rep = n_heads // n_kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        seen = pos[None, :] <= pos[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, n_heads * d)
        return x + ctx @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def moe_gates(x, ffn_norm, gate, turned, *, eps, top_k):
    """Normed input; for every token and expert the weight that expert
    gets (its softmax value over ALL experts if it is among the token's
    top_k, else zero); and for every token the router's margin: the last
    chosen expert's gate logit minus the first rejected one's.  Near 0
    the choice is a tie that rounding can turn; where ``turned`` [s] is
    set it is turned here too, and the first rejected expert takes the
    last chosen one's place with its own softmax value."""
    with jax.default_matmul_precision(HIGHEST):
        hn = rms_norm(x, ffn_norm, eps)
        logits = hn @ gate                                  # [s, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(logits, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        seats = jnp.broadcast_to(jnp.arange(top_k), idx[:, :top_k].shape)
        seats = seats.at[:, top_k - 1].set(
            jnp.where(turned, top_k, top_k - 1))
        idx = jnp.take_along_axis(idx, seats, axis=1)
        chosen = jnp.take_along_axis(probs, idx, axis=1)    # as they are
        dense = jnp.zeros_like(logits)
        dense = dense.at[jnp.arange(x.shape[0])[:, None], idx].set(chosen)
        return hn, dense, margin


@jax.jit
def expert_out(hn, gate_weight, w1, w2, w3):
    """One expert over every token, weighted by its gate (zero where the
    token did not choose it)."""
    with jax.default_matmul_precision(HIGHEST):
        y = (jax.nn.silu(hn @ w1) * (hn @ w3)) @ w2
        return y * gate_weight[:, None]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, norm, output, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm, eps) @ output.T


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32).  With a list for
    ``router_margins``, each layer appends its margins [s].  ``turned``
    maps a layer's index to the positions whose routing choice is turned
    there (see ``moe_gates``)."""
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    x = weights.embedding()[tokens].astype(jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        x = attention_block(
            x, w, n_heads=int(cfg["num_attention_heads"]),
            n_kv=int(cfg["num_key_value_heads"]),
            theta=float(cfg["rope_theta"]), eps=eps)
        mask = np.zeros(x.shape[0], bool)
        mask[list((turned or {}).get(i, ()))] = True
        hn, dense, margin = moe_gates(
            x, w["ffn_norm"], w["gate"], jnp.asarray(mask), eps=eps,
            top_k=int(cfg["num_experts_per_tok"]))
        if router_margins is not None:
            router_margins.append(margin)
        y = jnp.zeros_like(x)
        for e in range(int(cfg["num_local_experts"])):
            ew = weights.expert(i, e)
            y = y + expert_out(hn, dense[:, e], ew["w1"], ew["w2"], ew["w3"])
        x = x + y
        del w
    return head(x, weights.final_norm(), weights.output(), eps=eps)


def position_losses(logits, labels) -> jax.Array:
    """Cross entropy at every position [s] (float32)."""
    labels = jnp.asarray(np.asarray(labels, np.int32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def cross_entropy(logits, labels) -> jax.Array:
    """Summed cross entropy over positions (float32)."""
    return jnp.sum(position_losses(logits, labels))
