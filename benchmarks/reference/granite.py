"""Plain reference: the granite-4.0-h-small forward pass (``model_type``
``granitemoehybrid``, ``ibm-granite/granite-4.0-h-small``'s
``config.json``).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no chunk; one sequence at a time, one expert at a time, the
state-space recurrence as a plain ``lax.scan`` OVER TOKENS (the program
runs a chunked scan over blocks of 256 and a separate decode step; this
file knows neither, so it is independent of ``models/mamba.py``).  The
equations, for hidden 4096, RMSNorm with eps 1e-5, no bias but the
convolution's:

* embedding ``x = embedding_multiplier * E[token]`` (12); head: final
  RMSNorm, ``logits = (h E^T) / logits_scaling`` (16; tied);
* a layer: ``x = x + residual_multiplier * Mixer(RMSNorm(x))``, then
  ``x = x + residual_multiplier * (Routed(h) + Shared(h))`` with
  ``h = RMSNorm(x)`` (0.22 on both branches);
* the attention mixer (``layer_types[l] == 'attention'``): 32 query and
  8 key/value heads of 128, NO position embedding
  (``position_embedding_type`` ``nope``: nothing rotates, nothing is
  added), causal, every key, scores times ``attention_multiplier``
  0.0078125 (1/128, not 1/sqrt(128));
* the Mamba-2 mixer (``'mamba'``; inner width 8192 = 128 heads x 64,
  one group, state 128, four taps):
  1. ``[z | xBC | dt] = h W_in``: 8192 | 8448 | 128;
  2. ``xBC_t = silu(b + sum_j w[:, j] xBC_{t-3+j})`` over each of the
     8,448 channels (zeros before the sequence), then ``x_t`` [128, 64],
     ``B_t`` [128], ``C_t`` [128];
  3. ``delta_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``;
  4. ``S_t = exp(delta_t A) S_{t-1} + delta_t (x_t outer B_t)``,
     ``y_t = S_t C_t + D x_t``;
  5. ``y = RMSNorm_w(y * silu(z))`` over all 8,192 channels, ``y W_out``;
* routed experts: ``logits = h W_r`` (72) in float32, the ten largest,
  gates the softmax over those ten logits; expert e
  ``(silu(u[:768]) * u[768:]) W_out,e`` with ``u = h W_in,e``; the
  shared MLP the same form at 1536, every token, no gate.

DEPARTURES from the published code, each with its reason: (a) the
published mixer computes step 4 by a chunked algorithm
(``mamba_chunk_size`` 256) or a fused kernel; the recurrence here is
what both compute; (b) the published gated norm has a ``group_size`` of
the whole inner width at ``mamba_n_groups`` 1, which is the plain
RMSNorm used here; (c) which half of ``u`` the ``silu`` takes is a
convention under random weights: the first, as the published
``GraniteMoeHybridMLP`` / ``ParallelExperts`` chunk it; (d) ONE CHIP'S
SHARE of the experts: the router scores all 72 and only the experts
``weights`` holds (``cfg['num_local_experts']`` of them from
``cfg['experts_first']`` on) are computed, under the gates the router
gave over all ten choices: what the chip of the deployment computes
before the exchange (``held=None`` computes every expert the weights
have).

``router_margins``, ``routing`` and ``forced`` count every layer (all
are sparse).  A margin, and how far a given expert lies below the last
chosen one, are in router LOGITS.

``faults`` (a set of names) turns this file into a FAULTY reference, for
the readings the probe's limits rest on and for the tests' controls:
``embedding_one`` / ``residual_one`` / ``logits_one`` (that multiplier
left at 1), ``scale_sqrt_head`` (scores over sqrt(128)), ``rope_on``
(queries and keys rotated at theta 10000), ``no_D`` (the skip term
dropped), ``gate_after_norm`` (``RMSNorm(y) * silu(z)``), ``silu_second``
(an expert's ``silu`` on the second half of ``u``), ``no_conv_bias``,
``no_shared``, ``state_bf16`` (``S`` rounded to bf16 after every token),
``state_dropped_at_chunks`` (``S`` and the convolution's columns start
from zeros every 512 tokens, or ``cfg['fault_chunk']``: a chunk's state
not handed to the next),
``float8`` (weights and each layer's normed inputs rounded to e4m3, the
nearest precision below the stated bf16), ``bf16`` (the stated precision
itself).
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
FAULTS = ("embedding_one", "residual_one", "logits_one", "scale_sqrt_head",
          "rope_on", "no_D", "gate_after_norm", "silu_second",
          "no_conv_bias", "no_shared", "state_bf16",
          "state_dropped_at_chunks", "float8", "bf16")
PRECISION = frozenset({"float8", "bf16"})


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rounded(x, faults):
    """The precision faults: x as the named precision holds it."""
    if "float8" in faults:
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if "bf16" in faults:
        # reduce_precision: ``mamba_out``'s note on state_bf16 says why
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _rotate_half(x, positions, theta: float):
    """The ``rope_on`` fault: x [s, heads, d] rotated as a model WITH
    rotary positions would (halves i, i + d/2)."""
    d = x.shape[-1]
    freq = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + turned * sin


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps",
                                             "faults"))
def attention_inputs(x, w, *, n_heads, n_kv, eps, faults):
    """q [s, heads, d], k, v [s, kv heads, d] of one attention layer for
    one sequence x [s, h]: nothing rotates."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        w = {k: _rounded(v, faults) for k, v in w.items()}
        hn = _rounded(rms_norm(x, w["mixer_norm"], eps), faults)
        d = w["wq"].shape[1] // n_heads
        q = (hn @ w["wq"]).reshape(s, n_heads, d)
        k = (hn @ w["wk"]).reshape(s, n_kv, d)
        v = (hn @ w["wv"]).reshape(s, n_kv, d)
        if "rope_on" in faults:
            pos = jnp.arange(s)
            q, k = _rotate_half(q, pos, 1e4), _rotate_half(k, pos, 1e4)
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


def attention_out(x, w, cfg, faults=frozenset()):
    """x [s, h] -> what an attention layer's mixer gives [s, h] (before
    the residual multiplier), a block of queries at a time."""
    n_heads = int(cfg["num_attention_heads"])
    names = ("mixer_norm", "wq", "wk", "wv")
    q, k, v = attention_inputs(
        x, {n: w[n] for n in names}, n_heads=n_heads,
        n_kv=int(cfg["num_key_value_heads"]),
        eps=float(cfg["rms_norm_eps"]),
        faults=faults & (PRECISION | {"rope_on"}))
    scale = (1.0 / math.sqrt(q.shape[-1]) if "scale_sqrt_head" in faults
             else float(cfg["attention_multiplier"]))
    out = [attend_block(q[first:first + QUERY_BLOCK], k, v, first,
                        scale=scale)
           for first in range(0, x.shape[0], QUERY_BLOCK)]
    with jax.default_matmul_precision(HIGHEST):
        return jnp.concatenate(out) @ _rounded(w["wo"], faults & PRECISION)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "d_head", "d_state", "n_groups", "d_conv", "eps", "faults",
    "chunk"))
def mamba_out(x, w, *, n_heads, d_head, d_state, n_groups, d_conv, eps,
              faults, chunk=CHUNK):
    """x [s, h] -> (what a Mamba-2 layer's mixer gives [s, h] before the
    residual multiplier, the state ``S`` [heads, d_head, d_state] its
    last token leaves): steps 1-5 of the module docstring, the recurrence
    one token at a time."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        precision = faults & PRECISION
        w = {k: _rounded(v, precision) for k, v in w.items()}
        hn = _rounded(rms_norm(x, w["mixer_norm"], eps), precision)
        di, gs = n_heads * d_head, n_groups * d_state
        zxbcdt = hn @ w["in_proj"]
        z, xBC, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * gs],
                      zxbcdt[:, 2 * di + 2 * gs:])
        # the taps lie over the K - 1 columns before a token and its own
        t = jnp.arange(s)
        fresh = ((t % chunk)[:, None] + jnp.arange(d_conv)[None, :]
                 < d_conv - 1)                      # [s, K]: before a chunk
        ext = jnp.concatenate([jnp.zeros((d_conv - 1, xBC.shape[1])), xBC])
        acc = jnp.zeros_like(xBC)
        if "no_conv_bias" not in faults:
            acc = acc + w["conv_bias"]
        for j in range(d_conv):
            col = ext[j:j + s]
            if "state_dropped_at_chunks" in faults:
                col = jnp.where(fresh[:, j:j + 1], 0.0, col)
            acc = acc + col * w["conv_kernel"][:, j]
        xBC = jax.nn.silu(acc)
        xs = xBC[:, :di].reshape(s, n_heads, d_head)
        rep = n_heads // n_groups
        B = jnp.repeat(xBC[:, di:di + gs].reshape(s, n_groups, d_state),
                       rep, axis=1)
        C = jnp.repeat(xBC[:, di + gs:].reshape(s, n_groups, d_state),
                       rep, axis=1)
        delta = jax.nn.softplus(dt + w["dt_bias"])          # [s, heads]
        A = -jnp.exp(w["A_log"])

        def step(S, inp):
            x_t, B_t, C_t, d_t, first = inp
            if "state_dropped_at_chunks" in faults:
                S = jnp.where(first, 0.0, S)
            S = (jnp.exp(d_t * A)[:, None, None] * S
                 + (d_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
            if "state_bf16" in faults:
                # not astype there and back: a compiler that is allowed
                # excess precision drops that round trip (the TPU's does,
                # and this fault then read as the sound reference, PR 40)
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
            return S, jnp.einsum("hdn,hn->hd", S, C_t)

        last, y = jax.lax.scan(
            step, jnp.zeros((n_heads, d_head, d_state), jnp.float32),
            (xs, B, C, delta, t % chunk == 0))
        if "no_D" not in faults:
            y = y + w["D"][:, None] * xs
        y = y.reshape(s, di)
        if "gate_after_norm" in faults:
            y = rms_norm(y, w["gate_norm"], eps) * jax.nn.silu(z)
        else:
            y = rms_norm(y * jax.nn.silu(z), w["gate_norm"], eps)
        return y @ w["out_proj"], last


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def moe_gates(x, ffn_norm, gate, forced, *, eps, top_k):
    """Normed input; for every token and expert of the ROUTER's the
    weight that expert gets (the softmax over the token's top_k logits,
    zero elsewhere); the router's margin (the last chosen logit minus
    the first rejected one's); the experts chosen [s, top_k]; and how
    far below the last chosen logit the lowest of them lies.  A row of
    ``forced`` [s, top_k] that is not negative is taken for the token's
    experts as it stands (the gates still this router's own softmax over
    them)."""
    with jax.default_matmul_precision(HIGHEST):
        hn = rms_norm(x, ffn_norm, eps)
        logits = hn @ gate                                  # [s, R]
        top, idx = jax.lax.top_k(logits, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        idx = jnp.where(forced[:, :1] >= 0, forced, idx[:, :top_k])
        at = jnp.take_along_axis(logits, idx, axis=1)
        below = top[:, top_k - 1] - jnp.min(at, axis=1)
        dense = jnp.zeros_like(logits)
        dense = dense.at[jnp.arange(x.shape[0])[:, None], idx].set(
            jax.nn.softmax(at, axis=-1))
        return hn, dense, margin, idx, jnp.maximum(below, 0.0)


@functools.partial(jax.jit, static_argnames=("faults",))
def expert_out(hn, gate_weight, w1, w2, w3, *, faults=frozenset()):
    """One expert (or, with a weight of ones, the shared MLP) over every
    token, weighted by its gate: ``w1`` is the first half of ``W_in``
    (under the silu), ``w3`` the second."""
    with jax.default_matmul_precision(HIGHEST):
        precision = faults & PRECISION
        hn, w1, w2, w3 = (_rounded(a, precision) for a in (hn, w1, w2, w3))
        if "silu_second" in faults:
            w1, w3 = w3, w1
        y = (jax.nn.silu(hn @ w1) * (hn @ w3)) @ w2
        return y * gate_weight[:, None]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_block(x, norm, output_rows, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm, eps) @ output_rows.T


def held_experts(cfg: dict, routed: int) -> range:
    """The router's experts this share of the layer computes."""
    first = int(cfg.get("experts_first", 0))
    return range(first, min(first + int(cfg["num_local_experts"]), routed))


def moe_out(x, w, weights, cfg, i: int, forced_rows, faults, held=None):
    """x [s, h] -> (what layer i's experts and shared MLP give [s, h],
    margins, chosen, below).  ``held``: the router's experts computed
    (None: ``held_experts``); the shared MLP once."""
    s = x.shape[0]
    top_k = int(cfg["num_experts_per_tok"])
    given = np.full((s, top_k), -1, np.int32)
    for t, experts in forced_rows.items():
        given[t] = experts
    hn, dense, margin, chose, below = moe_gates(
        x, w["ffn_norm"], w["gate"], jnp.asarray(given),
        eps=float(cfg["rms_norm_eps"]), top_k=top_k)
    routed = w["gate"].shape[1]
    own = faults & (PRECISION | {"silu_second"})
    y = jnp.zeros_like(x)
    for e in (held_experts(cfg, routed) if held is None else held):
        ew = weights.expert(i, e)
        y = y + expert_out(hn, dense[:, e], ew["w1"], ew["w2"], ew["w3"],
                           faults=own)
    if "no_shared" not in faults:
        y = y + expert_out(hn, jnp.ones((s,), jnp.float32), w["shared_w1"],
                           w["shared_w2"], w["shared_w3"], faults=own)
    return y, margin, chose, below


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None, rows=None, faults=frozenset(),
                   routing: list = None, forced: dict = None,
                   states: list = None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32), or [len(rows), vocab]
    at the positions ``rows``.  With a list for ``router_margins`` each
    layer appends its margins [s].  With a list for ``routing`` each
    layer appends (the experts chosen [s, top_k], how far below its own
    last choice the lowest of them lies [s]); ``forced`` maps a layer's
    index to {position: experts}: the experts that token is given there,
    whatever this router would choose.  With a list for ``states`` each
    state-space layer appends the state its last token leaves.
    ``turned`` is the probe's other way of saying so and is not
    implemented here."""
    if turned:
        raise NotImplementedError("give the experts (forced), not a turn")
    tokens = np.asarray(tokens, np.int32)
    faults = frozenset(faults)
    assert faults <= set(FAULTS), faults
    eps = float(cfg["rms_norm_eps"])
    emb = 1.0 if "embedding_one" in faults else float(
        cfg["embedding_multiplier"])
    res = 1.0 if "residual_one" in faults else float(
        cfg["residual_multiplier"])
    x = emb * weights.embedding_rows(tokens)
    mamba = dict(n_heads=int(cfg["mamba_n_heads"]),
                 d_head=int(cfg["mamba_d_head"]),
                 d_state=int(cfg["mamba_d_state"]),
                 n_groups=int(cfg["mamba_n_groups"]),
                 d_conv=int(cfg["mamba_d_conv"]), eps=eps,
                 chunk=int(cfg.get("fault_chunk", CHUNK)))
    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        if cfg["layer_types"][i] == "mamba":
            names = ("mixer_norm", "in_proj", "conv_kernel", "conv_bias",
                     "dt_bias", "A_log", "D", "gate_norm", "out_proj")
            mixed, last = mamba_out(
                x, {n: w[n] for n in names}, **mamba,
                faults=faults & (PRECISION | {
                    "no_D", "gate_after_norm", "no_conv_bias", "state_bf16",
                    "state_dropped_at_chunks"}))
            if states is not None:
                states.append(last)
        else:
            mixed = attention_out(x, w, cfg, faults)
        x = x + res * mixed
        y, margin, chose, below = moe_out(
            x, w, weights, cfg, i, (forced or {}).get(i, {}), faults)
        if router_margins is not None:
            router_margins.append(margin)
        if routing is not None:
            routing.append((np.asarray(chose), np.asarray(below)))
        x = x + res * y
        del w
    if rows is not None:
        x = x[jnp.asarray(np.asarray(rows, np.int32))]
    norm = weights.final_norm()
    vocab = int(cfg["vocab_size"])
    logits = jnp.concatenate(
        [head_block(x, norm, weights.output_rows(v0, min(v0 + VOCAB_BLOCK,
                                                         vocab)), eps=eps)
         for v0 in range(0, vocab, VOCAB_BLOCK)], axis=-1)
    return logits if "logits_one" in faults else logits / float(
        cfg["logits_scaling"])


def position_losses(logits, labels) -> jax.Array:
    """Cross entropy at every position [s] (float32)."""
    labels = jnp.asarray(np.asarray(labels, np.int32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def cross_entropy(logits, labels) -> jax.Array:
    """Summed cross entropy over positions (float32)."""
    return jnp.sum(position_losses(logits, labels))
