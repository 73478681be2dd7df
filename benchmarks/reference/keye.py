"""Plain reference: the forward pass of Keye-VL-2.0-30B-A3B's language
model (``model_type`` ``KeyeVL2``; requests are text, the vision tower is
not built).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching; ``jax.lax.top_k`` for the selection.  One sequence at a time.
Layer input ``h_t``, ``x = RMSNorm(h_t)``, position ``t``:

* main heads: ``q_{t,i} = R_t(N(W_Q^i x))``, ``k_{t,g} = R_t(N(W_K^g x))``,
  ``v_{t,g} = W_V^g x``, 32 query and 4 key-value heads of 128.  ``N`` is
  an RMSNorm over the head's own values with one learned scale a layer for
  q and one for k.  ASSUMED (1): the config does not name a QK-norm;
  every size of the language model equals Qwen3-30B-A3B's, whose
  attention has this per-head form.
* ``R_t``: the rotary embedding, rotate-half (column i pairs with column
  i + d/2), theta 1e7, its d/2 frequency pairs dealt to three position
  streams in sections of 16, 24, 24 (``mrope_section``): pair i turns by
  ``pos[stream(i)] * theta^(-2i/d)``.  Written out in ``rotary`` below.
  A text token's three positions are all ``t``, which makes it the plain
  embedding; the streams are an argument so that a test can part them.
* indexer: ``qI_{t,j} = R_t(W_IQ^j x)``, 16 heads of 64;
  ``kI_t = R_t(LN(W_IK x))``, one head of 64;
  ``w_{t,j} = (W_Iw x)_j * 16^-1/2 * 64^-1/2``;
  ``I_{t,s} = sum_j w_{t,j} * relu(qI_{t,j} . kI_s)`` for ``s <= t``.
  ASSUMED (2): the indexer reads the layer's NORMED input through
  bias-free projections.  ASSUMED (3): the rotary embedding turns all 64
  dimensions of the indexer's query and key (the published DSA code
  rotates a 64-wide part of a wider head; here the head IS 64 wide), with
  the sections keeping their proportions (8, 12, 12 pairs).  ASSUMED (4):
  the LayerNorm (with bias) on the indexer's key, and ASSUMED (5): the
  two scale factors on ``w``, both from the published DSA code.
* ``S_t``: the positions of the 2,048 largest ``I_{t,s}`` over ``s <= t``;
  every ``s <= t`` while ``t < 2048``.  ``topk`` counts TOKENS; the
  config's two chunk sizes are tile sizes of the score computation.
  ASSUMED (6), the tie rule: of equal scores the earlier position first,
  which is ``jax.lax.top_k``'s rule.
* ``o_{t,i} = sum_{s in S_t} softmax_{s in S_t}(q_{t,i} . k_{s,g(i)} /
  sqrt(128)) v_{s,g(i)}``; the layer adds ``W_O [o_{t,1..32}]``.
* experts: ``p = softmax(W_r x')`` over all 128 in float32, the 8
  largest, gates renormalised to sum 1 (``norm_topk_prob`` true),
  ``y = sum_e g_e W_down^e(silu(W_gate^e x') * W_up^e x')`` added to the
  stream; ``x'`` the second RMSNorm's output.  No shared expert, no
  dense layer (``mlp_only_layers`` empty, ``decoder_sparse_step`` 1): the
  published dense ``intermediate_size`` shapes nothing.
* logits = RMSNorm(h_L) W_out^T (untied head).

Departures from the published layout, none of which changes a value: the
program under test rotates INTERLEAVED pairs, which is this model with
the columns of the four rotated projections and the entries of their
norms' parameters relabelled within each head
(``keye_from_program.py``); this file is rotate-half throughout.  Every
expert runs over every token weighted by its gate (zero where the token
did not choose it).

It runs in blocks so that it fits beside the program on the chip: the
queries of a layer in blocks of ``QUERY_BLOCK`` rows, an expert at a
time, the head over ``VOCAB_BLOCK`` rows of the vocabulary at a time
and only at the positions asked for.

``faults`` (a set of names) turns this file into a FAULTY reference, for
the readings the probe's limits rest on: ``dense`` (every ``s <= t``
attended), ``topk_half`` (half the keys selected), ``unweighted`` (every
``w_{t,j}`` equal), ``whole_qk_norm`` (the norm over the whole
projection, OLMoE's form), ``float8`` (weights and the layer's input
rounded to e4m3, the nearest precision below the stated bf16), ``bf16``
(the stated precision itself).
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


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rotary(x, streams, theta, sections):
    """x [s, heads, d], rotate-half; ``streams`` [3, s] the three position
    streams.  Pair i (columns i and i + d/2) follows the stream its
    section names: the first ``sections[0]`` pairs the first stream, and
    so on, the sections scaled to the head's d/2 pairs."""
    d = x.shape[-1]
    pairs = d // 2
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    bounds = np.round(np.cumsum(sections) * pairs / sum(sections))
    stream_of = np.searchsorted(bounds, np.arange(pairs), side="right")
    pos = streams.astype(jnp.float32)[stream_of, :].T           # [s, d/2]
    ang = pos * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    a, b = x[..., :pairs], x[..., pairs:]
    return x * cos + jnp.concatenate([-b, a], axis=-1) * sin


def _rounded(x, faults):
    """The precision faults: x as the named precision holds it."""
    if "float8" in faults:
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if "bf16" in faults:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "theta", "eps", "sections", "faults"))
def attention_inputs(x, w, streams, *, n_heads, n_kv, theta, eps, sections,
                     faults):
    """Everything of a layer's attention that is per token: q, k, v and
    the indexer's q, k, w for one sequence x [s, h]."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        w = {k: _rounded(v, faults) for k, v in w.items()}
        hn = _rounded(rms_norm(x, w["attention_norm"], eps), faults)
        d = w["wq"].shape[1] // n_heads
        q, k = hn @ w["wq"], hn @ w["wk"]
        if "whole_qk_norm" in faults:
            # OLMoE's form: the mean square over the whole projection
            q = rms_norm(q, jnp.tile(w["q_norm"], n_heads), eps)
            k = rms_norm(k, jnp.tile(w["k_norm"], n_kv), eps)
            q, k = q.reshape(s, n_heads, d), k.reshape(s, n_kv, d)
        else:
            # ASSUMED (1): RMSNorm of each head, one scale for all heads
            q = rms_norm(q.reshape(s, n_heads, d), w["q_norm"], eps)
            k = rms_norm(k.reshape(s, n_kv, d), w["k_norm"], eps)
        q = rotary(q, streams, theta, sections)
        k = rotary(k, streams, theta, sections)
        v = (hn @ w["wv"]).reshape(s, n_kv, d)
        # ASSUMED (2): the indexer reads the normed input, no bias
        di = w["index_wk"].shape[1]
        hi = w["index_wq"].shape[1] // di
        iq = (hn @ w["index_wq"]).reshape(s, hi, di)
        # ASSUMED (4): LayerNorm on the indexer's key
        ik = layer_norm(hn @ w["index_wk"], w["index_k_norm"],
                        w["index_k_bias"], eps)
        # ASSUMED (3): the rotary embedding over all of the indexer's head
        iq = rotary(iq, streams, theta, sections)
        ik = rotary(ik[:, None, :], streams, theta, sections)[:, 0, :]
        # ASSUMED (5): the two scale factors
        iw = (hn @ w["index_ww"]) * hi ** -0.5 * di ** -0.5
        if "unweighted" in faults:
            iw = jnp.full_like(iw, hi ** -0.5 * di ** -0.5)
        return q, k, v, iq, ik, iw


@functools.partial(jax.jit, static_argnames=("topk", "dense"))
def attend_block(q, k, v, iq, ik, iw, first, *, topk, dense):
    """A block of queries [bq, ...] at positions first.. over the whole
    sequence's keys: index scores, the topk largest over s <= t, softmax
    over the chosen keys only.  Returns [bq, heads * d]."""
    with jax.default_matmul_precision(HIGHEST):
        bq, n_heads, d = q.shape
        T, n_kv, _ = k.shape
        t = first + jnp.arange(bq)
        seen = jnp.arange(T)[None, :] <= t[:, None]              # s <= t
        score = jnp.einsum(
            "qj,qjs->qs", iw,
            jax.nn.relu(jnp.einsum("qjd,sd->qjs", iq, ik)))
        score = jnp.where(seen, score, -jnp.inf)
        if dense:
            chosen = seen
        else:
            # ASSUMED (6): lax.top_k puts the lower index first among
            # equal scores.  A query with fewer than topk keys behind it
            # draws -inf entries too: they are not seen, so not chosen
            _, idx = jax.lax.top_k(score, min(topk, T))
            chosen = jnp.zeros((bq, T), bool).at[
                jnp.arange(bq)[:, None], idx].set(True) & seen
        rep = n_heads // n_kv
        kk = jnp.repeat(k, rep, axis=1)
        vv = jnp.repeat(v, rep, axis=1)
        logits = jnp.einsum("qhd,shd->hqs", q, kk) / math.sqrt(d)
        logits = jnp.where(chosen[None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs, vv).reshape(bq, n_heads * d)


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def moe_gates(x, ffn_norm, gate, turned, forced, *, eps, top_k):
    """Normed input; for every token and expert the weight that expert
    gets (its softmax value over ALL experts, renormalised over the
    token's top_k, if it is among them, else zero); the router's margin
    (the last chosen expert's logit minus the first rejected one's); the
    experts chosen [s, top_k]; and how far below the last chosen
    expert's logit the lowest of them lies (0 where they are the
    router's own).  Where ``turned`` [s] is set the first rejected
    expert takes the last chosen one's place; a row of ``forced``
    [s, top_k] that is not negative is taken for the token's experts as
    it stands (the gates still this router's own values, renormalised
    over them)."""
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
        idx = jnp.where(forced[:, :1] >= 0, forced, idx)
        below = top[:, top_k - 1] - jnp.min(
            jnp.take_along_axis(logits, idx, axis=1), axis=1)
        chosen = jnp.take_along_axis(probs, idx, axis=1)
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


def attention_out(x, w, cfg, streams, faults=frozenset()):
    """x [s, h] -> W_O [o_1..o_32] [s, h]: what the layer's attention
    adds to the stream (a block of queries at a time)."""
    topk = int(cfg["sa_config"]["topk"])
    if "topk_half" in faults:
        topk = max(1, topk // 2)
    q, k, v, iq, ik, iw = attention_inputs(
        x, w, streams, n_heads=int(cfg["num_attention_heads"]),
        n_kv=int(cfg["num_key_value_heads"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        sections=tuple(cfg["rope_scaling"]["mrope_section"]),
        faults=frozenset(faults))
    s = x.shape[0]
    out = []
    for first in range(0, s, QUERY_BLOCK):
        rows = slice(first, min(first + QUERY_BLOCK, s))
        out.append(attend_block(q[rows], k, v, iq[rows], ik, iw[rows],
                                first, topk=topk, dense="dense" in faults))
    with jax.default_matmul_precision(HIGHEST):
        return jnp.concatenate(out) @ _rounded(w["wo"], frozenset(faults))


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None, rows=None, streams=None,
                   faults=frozenset(), attention_outputs: dict = None,
                   routing: list = None, forced: dict = None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32), or [len(rows), vocab]
    at the positions ``rows``.  ``streams`` [3, s]: the three position
    streams (None: text, all three 0..s-1).  With a list for
    ``router_margins`` each layer appends its margins [s]; ``turned`` maps
    a layer's index to the positions whose routing choice is turned
    there.  With a dict for ``attention_outputs`` whose keys are layer
    indices, each such layer leaves its attention's output [s, h] there.
    With a list for ``routing`` each layer appends (the experts chosen
    [s, top_k], how far below its own last choice the lowest of them
    lies [s]); ``forced`` maps a layer's index to {position: experts}: the
    experts that token is given there, whatever this router would choose
    (for reading what a program's own close choices explain)."""
    tokens = np.asarray(tokens, np.int32)
    s = len(tokens)
    faults = frozenset(faults)
    if streams is None:
        streams = np.broadcast_to(np.arange(s, dtype=np.int32), (3, s))
    streams = jnp.asarray(streams)
    x = weights.embedding_rows(tokens)
    eps = float(cfg["rms_norm_eps"])
    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        att = attention_out(x, w, cfg, streams, faults)
        if attention_outputs is not None and i in attention_outputs:
            attention_outputs[i] = att
        x = x + att
        mask = np.zeros(s, bool)
        mask[list((turned or {}).get(i, ()))] = True
        top_k = int(cfg["num_experts_per_tok"])
        given = np.full((s, top_k), -1, np.int32)
        for t, experts in (forced or {}).get(i, {}).items():
            given[t] = experts
        hn, dense, margin, chose, below = moe_gates(
            x, w["ffn_norm"], w["gate"], jnp.asarray(mask),
            jnp.asarray(given), eps=eps, top_k=top_k)
        if router_margins is not None:
            router_margins.append(margin)
        if routing is not None:
            routing.append((np.asarray(chose), np.asarray(below)))
        y = jnp.zeros_like(x)
        for e in range(int(cfg["num_local_experts"])):
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
