"""Plain reference: the Mistral-7B and Mixtral-8x7B forward pass.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no capacity.  It follows the published models (Mistral AI's
reference implementation, ``mistral-src``: ``model.py`` and
``moe.py``):

* RMSNorm:  x * rsqrt(mean(x^2) + eps) * w
* attention: wq/wk/wv without bias, rotary embedding over interleaved
  pairs (the complex product of the reference implementation), grouped
  queries (each KV head serves n_heads / n_kv_heads query heads), causal
  mask, and for Mistral a sliding window: position i sees j with
  i - window < j <= i
* MLP: w2(silu(w1 x) * w3 x)
* Mixtral: gate logits -> top 2 -> softmax over the two chosen (which is
  the softmax over all experts renormalised over the chosen two); each
  token's output is the weighted sum of its two experts.  No token is
  ever dropped.
* logits = norm(h) @ output^T; the loss is the mean cross entropy.

Weights come one layer at a time (``weights.layer(i)``) so that a
float32 copy of only one layer (for Mixtral: one expert) is on the chip
beside the program's own bf16 weights.
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
    """x [s, heads, d]: rotate interleaved pairs (x[2i], x[2i+1]) by
    positions * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]    # [s, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta",
                                             "window", "eps"))
def attention_block(x, w, *, n_heads, n_kv, theta, window, eps):
    """x [s, h] -> x + attention(norm(x)) for one sequence."""
    with jax.default_matmul_precision(HIGHEST):
        s, _ = x.shape
        d = w["wq"].shape[1] // n_heads
        hn = rms_norm(x, w["attention_norm"], eps)
        pos = jnp.arange(s)
        q = rotary((hn @ w["wq"]).reshape(s, n_heads, d), pos, theta)
        k = rotary((hn @ w["wk"]).reshape(s, n_kv, d), pos, theta)
        v = (hn @ w["wv"]).reshape(s, n_kv, d)
        rep = n_heads // n_kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        i, j = pos[:, None], pos[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, n_heads * d)
        return x + ctx @ w["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_mlp_block(x, w, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        hn = rms_norm(x, w["ffn_norm"], eps)
        return x + (jax.nn.silu(hn @ w["w1"]) * (hn @ w["w3"])) @ w["w2"]


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def moe_gates(x, ffn_norm, gate, turned, *, eps, top_k):
    """Normed input; for every token and expert the weight that expert
    gets (zero unless it is among the token's top_k); and for every
    token the router's margin: the last chosen expert's gate logit minus
    the first rejected one's.  Near 0 the choice is a tie that rounding
    can turn; where ``turned`` [s] is set the tie is turned here too, and
    the first rejected expert takes the last chosen one's place."""
    with jax.default_matmul_precision(HIGHEST):
        hn = rms_norm(x, ffn_norm, eps)
        logits = hn @ gate                                  # [s, E]
        top, idx = jax.lax.top_k(logits, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        seats = jnp.broadcast_to(jnp.arange(top_k), top[:, :top_k].shape)
        seats = seats.at[:, top_k - 1].set(
            jnp.where(turned, top_k, top_k - 1))
        top = jnp.take_along_axis(top, seats, axis=1)
        idx = jnp.take_along_axis(idx, seats, axis=1)
        weights = jax.nn.softmax(top, axis=-1)
        dense = jnp.zeros_like(logits)
        dense = dense.at[jnp.arange(x.shape[0])[:, None], idx].set(weights)
        return hn, dense, margin


@jax.jit
def expert_out(hn, gate_weight, w1, w2, w3):
    """One expert over every token, weighted by its gate (zero where the
    token did not choose it): plain and exact, and cheap at a probe's
    few hundred tokens."""
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
    ``router_margins``, each routed layer appends its margins [s].
    ``turned`` maps a layer's index to the positions whose routing tie
    is turned there (see ``moe_gates``)."""
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    x = weights.embedding()[tokens].astype(jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        x = attention_block(
            x, w, n_heads=int(cfg["num_attention_heads"]),
            n_kv=int(cfg["num_key_value_heads"]),
            theta=float(cfg["rope_theta"]),
            window=(int(cfg["sliding_window"])
                    if cfg.get("sliding_window") else None), eps=eps)
        if "gate" in w:
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
                y = y + expert_out(hn, dense[:, e], ew["w1"], ew["w2"],
                                   ew["w3"])
            x = x + y
        else:
            x = dense_mlp_block(x, w, eps=eps)
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
