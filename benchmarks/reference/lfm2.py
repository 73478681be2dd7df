"""Plain reference: the LFM2-8B-A1B forward pass (``model_type``
``lfm2_moe``, ``LiquidAI/LFM2-8B-A1B``'s ``config.json`` and the family's
published ``transformers`` code, ``models/lfm2_moe/modeling_lfm2_moe.py``).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no chunk; one sequence at a time, a block of queries at a time
against the whole sequence's keys, one expert at a time, the convolution
as ``conv_L_cache`` SHIFTED SUMS over the whole sequence (the program
runs a chunk from a carried state and a separate decode step over a
slot's two columns; this file knows neither, so it is independent of
``models/short_conv.py``).  The equations, for hidden 2048, RMSNorm with
``norm_eps`` 1e-5 everywhere, no bias anywhere, by the config's own keys:

* ``x0 = E[ids]``; layer l of kind ``layer_types[l]``:
  ``x = x + mixer(N_op(x))`` then ``x = x + ffn(N_ffn(x))``;
  ``logits = N_final(x) E^T``: the head is TIED to the embedding and the
  published ``embedding_norm`` is the model's LAST norm;
* ``conv``: ``[B | C | X] = u W_in`` (three times the hidden width, in
  that order), ``z = B * X``, ``c_t = sum_j w[:, j] z_{t-(K-1)+j}`` over
  each channel's ``K = conv_L_cache`` (3) taps, causal, zeros before the
  sequence, ``conv_bias`` false, NO activation; ``o = (C * c) W_out``.
  What a request carries from token to token: ``z`` at its last ``K - 1``
  tokens;
* ``full_attention``: 32 query heads against 8 key/value heads of
  ``hidden / heads`` = 64 (query head i against ``i // 4``); each query
  and key head's 64 values RMSNorm'd by themselves under ONE scale of 64
  for the queries and one for the keys; q and k turn by the plain rotary
  embedding (theta ``rope_theta`` 1e6; rotate-half: within a head column
  i pairs with column i + 32); causal softmax of ``q k^T / 8``; ``W_o``;
* ffn of the first ``num_dense_layers`` (2) layers: SwiGLU at
  ``intermediate_size`` 7,168;
* ffn of the others, over ``m = N_ffn(x)``: ``s = sigmoid(m W_r)`` in
  float32 over the ``num_experts`` (32); the ``num_experts_per_tok`` (4)
  chosen are the largest of ``s + b`` (``b`` the ``expert_bias`` buffer,
  ``use_expert_bias``: the CHOICE only); gates ``s`` at the chosen
  experts ``/ (their sum + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor`` (1.0); ``sum_e g_e SwiGLU_e(m)`` at
  ``moe_intermediate_size`` 1,792, no shared expert.

``router_margins``, ``routing`` and ``forced`` count SPARSE layers: entry
0 is the model's layer ``num_dense_layers`` (the engine's routing record
has a row a sparse layer).  A margin, and how far a given expert lies
below the last chosen one, are in the units of the CHOICE: score plus
bias.

DEPARTURES from the published code, none of which changes a value: every
expert runs over every token, weighted by its gate (zero where the token
did not choose it); the published convolution is a ``Conv1d`` with
``groups = hidden`` and left padding, which is the shifted sums here; the
program under test rotates INTERLEAVED pairs, which is this model with
the columns of W_q and W_k (and the entries of the two head scales)
relabelled within each head (``lfm2_from_program.py``).

``faults`` (a set of names) turns this file into a FAULTY reference, for
the readings the probe's limits rest on and for the tests' controls:
``taps_reversed`` (tap j applied where tap K-1-j belongs),
``state_dropped_at_chunks`` (the carried columns are zeros at every 512th
token, or ``cfg['fault_chunk']``: a state not handed from one chunk to
the next), ``bc_swapped`` (``B`` and ``C`` exchanged: ``z = C * X``,
gated by ``B``), ``conv_activation`` (a silu on the convolution's
output), ``bias_in_gates`` (the choice bias added to the gates),
``no_qk_norm``, ``no_rope`` (the attention layers do not rotate),
``kv_neighbour`` (query head i attends key/value head ``(i // 4) ^ 1``:
the other head of its 128-lane row of the pool), ``state_float8`` (the
columns a token reads from BEFORE itself rounded to e4m3: a state kept
below bf16), ``norm_max`` (gates over ``max(sum, 1e-6)``: NOT told apart,
the sums are of order 1), ``dense_layer_sparse`` (the LAST dense layer
runs the first sparse layer's experts in place of its own MLP),
``float8`` (weights and each sublayer's normed inputs rounded to e4m3,
the nearest precision below the stated bf16), ``bf16`` (the stated
precision itself).
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
CHUNK = 512
FAULTS = ("taps_reversed", "state_dropped_at_chunks", "bc_swapped",
          "conv_activation", "bias_in_gates", "no_qk_norm", "no_rope",
          "kv_neighbour", "state_float8", "norm_max", "dense_layer_sparse",
          "float8", "bf16")
PRECISION = frozenset({"float8", "bf16"})
CONV_FAULTS = frozenset({"taps_reversed", "state_dropped_at_chunks",
                         "bc_swapped", "conv_activation", "state_float8"})
ATTENTION_FAULTS = frozenset({"no_qk_norm", "no_rope", "kv_neighbour"})


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


def _float8(x):
    # reduce_precision: the TPU's compiler drops an ``astype`` there and
    # back
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _rounded(x, faults):
    """The precision faults: x as the named precision holds it."""
    if "float8" in faults:
        return _float8(x)
    if "bf16" in faults:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


@functools.partial(jax.jit, static_argnames=("taps", "faults", "chunk"))
def conv_out(hn, w, *, taps, faults, chunk=CHUNK):
    """The normed input hn [s, h] -> (what a conv layer's mixer gives
    [s, h], the columns ``z`` [taps - 1, h] its last tokens leave)."""
    with jax.default_matmul_precision(HIGHEST):
        s, h = hn.shape
        precision = faults & PRECISION
        w = {k: _rounded(v, precision) for k, v in w.items()}
        hn = _rounded(hn, precision)
        bcx = hn @ w["in_proj"]
        B, C, X = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
        if "bc_swapped" in faults:
            B, C = C, B
        z = B * X
        t = jnp.arange(s)
        # [s, K]: tap j of token t reads a column from before t's chunk
        fresh = ((t % chunk)[:, None] + jnp.arange(taps)[None, :]
                 < taps - 1)
        ext = jnp.concatenate([jnp.zeros((taps - 1, h)), z])
        acc = jnp.zeros_like(z)
        for j in range(taps):
            col = ext[j:j + s]                  # z_{t - (taps - 1) + j}
            if j < taps - 1:
                # a column a token reads from before itself: carried
                if "state_float8" in faults:
                    col = _float8(col)
                if "state_dropped_at_chunks" in faults:
                    col = jnp.where(fresh[:, j:j + 1], 0.0, col)
            tap = taps - 1 - j if "taps_reversed" in faults else j
            acc = acc + col * w["conv_kernel"][:, tap]
        if "conv_activation" in faults:
            acc = jax.nn.silu(acc)
        # the last taps - 1 columns (zeros before a short sequence)
        return (C * acc) @ w["out_proj"], ext[s:]


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta",
                                             "eps", "faults"))
def attention_inputs(hn, w, *, n_heads, n_kv, theta, eps, faults):
    """q [s, heads, d], k, v [s, kv heads, d] of one attention layer for
    one sequence's normed input hn [s, h]: each head normed by itself,
    then rotated."""
    with jax.default_matmul_precision(HIGHEST):
        s = hn.shape[0]
        w = {k: _rounded(v, faults) for k, v in w.items()}
        hn = _rounded(hn, faults)
        d = w["wq"].shape[1] // n_heads
        q = (hn @ w["wq"]).reshape(s, n_heads, d)
        k = (hn @ w["wk"]).reshape(s, n_kv, d)
        v = (hn @ w["wv"]).reshape(s, n_kv, d)
        if "no_qk_norm" not in faults:
            q = rms_norm(q, w["q_norm"], eps)
            k = rms_norm(k, w["k_norm"], eps)
        if "no_rope" not in faults:
            pos = jnp.arange(s)
            q, k = rotary(q, pos, theta), rotary(k, pos, theta)
        if "kv_neighbour" in faults:
            other = jnp.arange(n_kv) ^ 1
            k, v = k[:, other], v[:, other]
        return q, k, v


@functools.partial(jax.jit, static_argnames=("scale",))
def attend_block(q, k, v, first, *, scale):
    """A block of queries [bq, heads, d] at positions ``first ..`` over
    the whole sequence's keys, causal.  Returns [bq, heads * d]."""
    with jax.default_matmul_precision(HIGHEST):
        bq, n_heads, d = q.shape
        rep = n_heads // k.shape[1]
        seen = (jnp.arange(k.shape[0])[None, :]
                <= (first + jnp.arange(bq))[:, None])
        scores = jnp.einsum("qhd,khd->hqk", q,
                            jnp.repeat(k, rep, axis=1)) * scale
        scores = jnp.where(seen[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs,
                          jnp.repeat(v, rep, axis=1)).reshape(bq, n_heads * d)


def attention_out(hn, w, cfg, faults=frozenset()):
    """The normed input hn [s, h] -> what an attention layer's mixer
    gives [s, h], a block of queries at a time."""
    names = ("wq", "wk", "wv", "q_norm", "k_norm")
    q, k, v = attention_inputs(
        hn, {n: w[n] for n in names},
        n_heads=int(cfg["num_attention_heads"]),
        n_kv=int(cfg["num_key_value_heads"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["norm_eps"]),
        faults=faults & (PRECISION | ATTENTION_FAULTS))
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = [attend_block(q[first:first + QUERY_BLOCK], k, v, first,
                        scale=scale)
           for first in range(0, hn.shape[0], QUERY_BLOCK)]
    with jax.default_matmul_precision(HIGHEST):
        return jnp.concatenate(out) @ _rounded(w["wo"], faults & PRECISION)


@functools.partial(jax.jit, static_argnames=("faults",))
def swiglu_out(hn, gate_weight, w1, w3, w2, *, faults=frozenset()):
    """One SwiGLU MLP (an expert, or with a weight of ones a dense
    layer's) over every token, weighted by its gate."""
    with jax.default_matmul_precision(HIGHEST):
        hn, w1, w3, w2 = (_rounded(a, faults) for a in (hn, w1, w3, w2))
        return ((jax.nn.silu(hn @ w1) * (hn @ w3)) @ w2) * gate_weight[:, None]


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "faults"))
def moe_gates(hn, gate, bias, forced, *, top_k, scale, faults):
    """For every token and expert the weight that expert gets (``scale``
    times the token's chosen sigmoid scores over their sum + 1e-6, zero
    elsewhere); the router's margin (the last chosen ``s + b`` minus the
    first rejected one's); the experts chosen [s, top_k]; and how far
    below the last chosen ``s + b`` the lowest of them lies.  A row of
    ``forced`` [s, top_k] that is not negative is taken for the token's
    experts as it stands (the gates still this router's own scores of
    them)."""
    with jax.default_matmul_precision(HIGHEST):
        scores = jax.nn.sigmoid(hn @ gate)                  # [s, E]
        choice = scores + bias
        top, idx = jax.lax.top_k(choice, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        idx = jnp.where(forced[:, :1] >= 0, forced, idx[:, :top_k])
        below = top[:, top_k - 1] - jnp.min(
            jnp.take_along_axis(choice, idx, axis=1), axis=1)
        at = jnp.take_along_axis(
            choice if "bias_in_gates" in faults else scores, idx, axis=1)
        total = jnp.sum(at, axis=-1, keepdims=True)
        at = at / (jnp.maximum(total, 1e-6) if "norm_max" in faults
                   else total + 1e-6) * scale
        dense = jnp.zeros_like(scores)
        dense = dense.at[jnp.arange(hn.shape[0])[:, None], idx].set(at)
        return dense, margin, idx, jnp.maximum(below, 0.0)


def moe_out(hn, w, weights, cfg, i: int, forced_rows, faults):
    """The normed input hn [s, h] -> (what sparse layer i's experts give
    [s, h], margins, chosen, below)."""
    s = hn.shape[0]
    top_k = int(cfg["num_experts_per_tok"])
    given = np.full((s, top_k), -1, np.int32)
    for t, experts in forced_rows.items():
        given[t] = experts
    dense, margin, chose, below = moe_gates(
        hn, w["gate"], w["choice_bias"], jnp.asarray(given), top_k=top_k,
        scale=float(cfg.get("routed_scaling_factor", 1.0)),
        faults=faults & {"bias_in_gates", "norm_max"})
    y = jnp.zeros_like(hn)
    for e in range(w["gate"].shape[1]):
        ew = weights.expert(i, e)
        y = y + swiglu_out(hn, dense[:, e], ew["w1"], ew["w3"], ew["w2"],
                           faults=faults & PRECISION)
    return y, margin, chose, below


@functools.partial(jax.jit, static_argnames=("eps",))
def normed(x, w, *, eps):
    return rms_norm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_block(x, norm, output_rows, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm, eps) @ output_rows.T


def kinds_of(cfg: dict) -> list:
    """The kind of each layer as run: the first ``num_hidden_layers`` of
    ``layer_types``."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None, rows=None, faults=frozenset(),
                   routing: list = None, forced: dict = None,
                   states: list = None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32), or [len(rows), vocab]
    at the positions ``rows``.  With a list for ``router_margins`` each
    SPARSE layer appends its margins [s].  With a list for ``routing``
    each sparse layer appends (the experts chosen [s, top_k], how far
    below its own last choice the lowest of them lies [s]); ``forced``
    maps a sparse layer's index AMONG THE SPARSE LAYERS to {position:
    experts}: the experts that token is given there, whatever this
    router would choose.  With a list for ``states`` each conv layer
    appends the columns ``z`` [taps - 1, h] its last tokens leave.
    ``turned`` is the probe's other way of saying so and is not
    implemented here."""
    if turned:
        raise NotImplementedError("give the experts (forced), not a turn")
    tokens = np.asarray(tokens, np.int32)
    faults = frozenset(faults)
    assert faults <= set(FAULTS), faults
    eps = float(cfg["norm_eps"])
    dense_layers = int(cfg["num_dense_layers"])
    taps = int(cfg["conv_L_cache"])
    precision = faults & PRECISION
    x = weights.embedding_rows(tokens)
    ones = jnp.ones((len(tokens),), jnp.float32)
    for i, kind in enumerate(kinds_of(cfg)):
        w = weights.layer(i)
        hn = normed(x, w["operator_norm"], eps=eps)
        if kind == "conv":
            y, last = conv_out(
                hn, {n: w[n] for n in ("in_proj", "conv_kernel", "out_proj")},
                taps=taps, faults=faults & (PRECISION | CONV_FAULTS),
                chunk=int(cfg.get("fault_chunk", CHUNK)))
            if states is not None:
                states.append(last)
        else:
            y = attention_out(hn, w, cfg, faults)
        x = x + y
        m = normed(x, w["ffn_norm"], eps=eps)
        sparse = i - dense_layers
        if "dense_layer_sparse" in faults and i == dense_layers - 1:
            # this dense layer runs the first sparse layer's experts
            f = moe_out(m, weights.layer(dense_layers), weights, cfg,
                        dense_layers, {}, faults)[0]
        elif sparse < 0:
            f = swiglu_out(m, ones, w["w1"], w["w3"], w["w2"],
                           faults=precision)
        else:
            f, margin, chose, below = moe_out(
                m, w, weights, cfg, i, (forced or {}).get(sparse, {}),
                faults)
            if router_margins is not None:
                router_margins.append(margin)
            if routing is not None:
                routing.append((np.asarray(chose), np.asarray(below)))
        x = x + f
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
