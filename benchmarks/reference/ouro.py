"""Plain reference: the Ouro forward pass (ByteDance Ouro-2.6B,
``model_type`` ``ouro``): a stack whose layers run ``total_ut_steps``
times over shared weights.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching.  ``x`` a token's stream, ``x = E[token]`` (no multiplier);
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``, ``w`` itself.

* For pass t = 1..T, for layer l = 1..L (the SAME weights in every pass):

  - ``u = RMSNorm_l1(x)``; ``q, k, v = u W_q, u W_k, u W_v``, a key-value
    head a query head, no bias, no norm on q or k; every dimension of q
    and k rotates at ``rope_theta`` on HALVES ``(i, i + d/2)`` (the
    program rotates interleaved pairs: ``ouro_from_program.py`` relabels
    the columns of W_q and W_k);
  - ``s[i, j] = q_i . k_j / sqrt(d)`` for ``j <= i``, softmax, where
    ``k_j, v_j`` are position j's keys and values of the same layer IN
    THE SAME PASS.  There is no cache here: a pass computes its keys from
    its own stream, so pass t's attention is over pass t's keys by
    construction, which is the published cache's index
    ``(t - 1) L + l``;
  - ``x = x + RMSNorm_l2(concat_h(o_h) W_o)``;
  - ``m = RMSNorm_l3(x)``;
    ``x = x + RMSNorm_l4((silu(m W_gate) * (m W_up)) W_down)``.

* After the last layer of EACH pass: ``x = RMSNorm_f(x)`` (one set of
  weights); this normed x is the next pass's input, and the exit gate
  reads it: ``lambda_t = sigmoid(x . w_g + b_g)``.
* Logits: the LAST pass's normed x times the untied head.
* The exit distribution (``exit_distribution``):
  ``p_t = lambda_t prod_{i<t} (1 - lambda_i)`` for t < T,
  ``p_T = prod_{i<T} (1 - lambda_i)``; a token leaves at the first t with
  ``p_1 + .. + p_t >= early_exit_threshold`` (``exit_pass``): at the
  published 1.0 that is T.
* Nothing depends on t but which keys a pass reads: no pass embedding, no
  pass's own norm.

Departures from the published description: none is intended.  What the
catalog row's keys do not themselves say is ASSUMED from
``modeling_ouro.py`` as recollected (the file is not in the repository)
and listed in ``configs/ouro-2.6b-serve.json`` under ``assumed``: the
four norms' placement, the final norm inside the loop, the cache's
index, the gate as a linear layer with a bias over the normed stream,
the logits of the last pass, rotary on halves, plain ``w`` in the norms.

``faults`` makes the named departures the probe's tolerances must tell
(``ouro_controls.py``): ``shared_planes`` (one plane a layer shared by
the passes, the paper's sharing at decode time: a token's keys and
values are those its LAST pass wrote, read by every pass of every later
token, so pass t of a query attends the last pass's keys of the tokens
before it and its own of itself), ``previous_plane`` (pass t attends, of the
tokens before a query, the keys pass t - 1 wrote; the first pass its
own), ``three_passes``,
``norm_once`` (the final norm after the last pass alone),
``no_output_norms``, ``theta_1e4``, ``float8`` (every normed activation
rounded to float8 e4m3, the nearest precision below the stated bf16),
``bf16`` (the same to bf16: what the program itself does).

Weights come one layer at a time (``weights.layer(i)``); the head is
computed at the rows that are compared alone.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
FAULTS = ("shared_planes", "previous_plane", "three_passes", "norm_once",
          "no_output_norms", "theta_1e4", "float8", "bf16")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rounded(x, faults):
    """``x`` as a program of a lower precision would hand it on.
    ``lax.reduce_precision``: the TPU's compiler drops an ``astype``
    there and back."""
    if "float8" in faults:
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    if "bf16" in faults:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def rotary_halves(x, positions, theta):
    """x [s, heads, d]: rotate the pairs (x[i], x[i + d/2]) by
    positions * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]    # [s, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_heads", "theta", "eps",
                                             "faults"))
def keys_values(x, w, *, n_heads, theta, eps, faults):
    """(the layer's normed input, k [s, heads, d] rotated, v) of a
    pass's stream ``x`` [s, h]."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        u = rounded(rms_norm(x, w["attention_norm"], eps), faults)
        pos = jnp.arange(s)
        k = rotary_halves((u @ w["wk"]).reshape(s, n_heads, -1), pos, theta)
        v = (u @ w["wv"]).reshape(s, n_heads, -1)
        return u, k, v


@functools.partial(jax.jit, static_argnames=("n_heads", "theta", "eps",
                                             "faults", "block"))
def attention_block(x, u, k, v, k_own, v_own, w, *, n_heads, theta, eps,
                    faults, block=512):
    """x [s, h] -> x + norm(attention(u)) for one sequence, the queries
    in blocks of ``block`` rows.  Position i attends ``k[j], v[j]`` for
    j < i and ``k_own[i], v_own[i]`` of itself: the same arrays where a
    pass reads its own keys, which is the model."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        d = k.shape[-1]
        pos = jnp.arange(s)
        q = rotary_halves((u @ w["wq"]).reshape(s, n_heads, d), pos, theta)
        out = []
        for first in range(0, s, block):
            qb, pb = q[first:first + block], pos[first:first + block]
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
            own = jnp.einsum("qhd,qhd->hq", qb,
                             k_own[first:first + block]) / math.sqrt(d)
            before = pos[None, :] < pb[:, None]
            scores = jnp.where(before[None], scores, -jnp.inf)
            top = jnp.maximum(scores.max(axis=-1), own)
            e = jnp.exp(scores - top[..., None])
            e_own = jnp.exp(own - top)
            ctx = (jnp.einsum("hqk,khd->qhd", e, v)
                   + e_own.T[..., None] * v_own[first:first + block])
            ctx = ctx / (e.sum(axis=-1) + e_own).T[..., None]
            out.append(ctx.reshape(-1, n_heads * d))
        y = jnp.concatenate(out, axis=0) @ w["wo"]
        if "no_output_norms" not in faults:
            y = rms_norm(y, w["attention_out_norm"], eps)
        return x + y


@functools.partial(jax.jit, static_argnames=("eps", "faults"))
def mlp_block(x, w, *, eps, faults):
    with jax.default_matmul_precision(HIGHEST):
        m = rounded(rms_norm(x, w["ffn_norm"], eps), faults)
        y = (jax.nn.silu(m @ w["w1"]) * (m @ w["w3"])) @ w["w2"]
        if "no_output_norms" not in faults:
            y = rms_norm(y, w["mlp_out_norm"], eps)
        return x + y


@functools.partial(jax.jit, static_argnames=("eps",))
def pass_end(x, norm, gate_w, gate_b, *, eps):
    """(the final norm of a pass's stream, the exit gate over it)."""
    with jax.default_matmul_precision(HIGHEST):
        x = rms_norm(x, norm, eps)
        return x, jax.nn.sigmoid(x @ gate_w + gate_b)


def exit_distribution(gates):
    """[T, s] from the gates [T, s]: ``p_t = gate_t prod_{i<t}
    (1 - gate_i)`` for t < T, and the last pass takes what is left."""
    gates = jnp.asarray(gates)
    p, left = [], jnp.ones_like(gates[0])
    for g in gates[:-1]:
        p.append(g * left)
        left = left * (1.0 - g)
    return jnp.stack(p + [left])


def exit_pass(p, threshold: float):
    """[s]: the pass (1-based) each token leaves at: the first whose
    running sum of ``p`` reaches ``threshold``; the last at 1.0, whatever
    rounding leaves the sum at."""
    reached = np.cumsum(np.asarray(p, np.float64), axis=0) >= threshold
    reached[-1] = True
    return reached.argmax(axis=0) + 1


def _passes(weights, cfg: dict, tokens, faults, read_planes=None):
    """(the last pass's normed stream [s, h], the exit gates [T, s], the
    last pass's keys and values a layer).  ``read_planes``: the keys and
    values a layer that EVERY pass reads for the tokens before a query,
    in its own pass's place (a fault: there is no such thing in the
    model)."""
    L = int(cfg["num_hidden_layers"])
    T = int(cfg["total_ut_steps"]) - ("three_passes" in faults)
    kw = dict(n_heads=int(cfg["num_attention_heads"]),
              theta=1e4 if "theta_1e4" in faults else float(
                  cfg["rope_theta"]),
              eps=float(cfg["rms_norm_eps"]),
              faults=faults & {"float8", "bf16"})
    own = faults & {"no_output_norms", "float8", "bf16"}
    x = weights.embedding_rows(tokens)
    norm, (gate_w, gate_b) = weights.final_norm(), weights.exit_gate()
    exits, wrote = [], [None] * L
    for t in range(T):
        for l in range(L):
            w = weights.layer(l)
            u, k, v = keys_values(x, w, **kw)
            before = itself = (k, v)
            if read_planes is not None:
                before = read_planes[l]
            elif "previous_plane" in faults and t:
                before = wrote[l]
            wrote[l] = (k, v)
            x = attention_block(x, u, *before, *itself, w,
                                **{**kw, "faults": own})
            x = mlp_block(x, w, eps=kw["eps"], faults=own)
        if t == T - 1 or "norm_once" not in faults:
            x, gate = pass_end(x, norm, gate_w, gate_b, eps=kw["eps"])
            exits.append(gate)
    return x, jnp.stack(exits), wrote


def forward_logits(weights, cfg: dict, tokens, rows=None,
                   faults=frozenset(), gates: list = None):
    """Logits [rows, vocab] (float32) of one sequence ``tokens`` [s] at
    ``rows`` (every position where None).  ``gates``, where a list, is
    given the exit gates [T, s] of the passes."""
    faults = frozenset(faults)
    unknown = faults - set(FAULTS)
    if unknown:
        raise ValueError(f"no such fault: {sorted(unknown)}")
    tokens = np.asarray(tokens, np.int32)
    planes = None
    if "shared_planes" in faults:
        # what one plane a layer holds of the tokens before a query once
        # they are done: their LAST pass's keys and values (here the sound
        # model's: the faulty model's own would take a token at a time)
        planes = _passes(weights, cfg, tokens, faults)[2]
    x, exits, _wrote = _passes(weights, cfg, tokens, faults, planes)
    if gates is not None:
        gates.append(exits)
    rows = np.arange(len(tokens)) if rows is None else np.asarray(rows)
    x = x[jnp.asarray(rows, jnp.int32)]
    V = int(cfg["vocab_size"])
    with jax.default_matmul_precision(HIGHEST):
        return jnp.concatenate(
            [x @ weights.output_rows(first, min(first + 8192, V)).T
             for first in range(0, V, 8192)], axis=-1)


def position_losses(logits, labels):
    """Cross entropy at each position: logits [s, V], labels [s]."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(labels)[:, None], axis=-1)[:, 0]
    return lse - picked


def cross_entropy(logits, labels):
    return jnp.mean(position_losses(logits, labels))
