"""Plain reference: the Qwen3-Next forward pass (``model_type``
``qwen3_next``, ``Qwen/Qwen3-Next-80B-A3B-Instruct``'s ``config.json``
and the public ``modeling_qwen3_next.py`` of ``transformers``).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no chunk; one sequence at a time, one expert at a time, the
delta rule as a plain ``lax.scan`` OVER TOKENS (the program solves a
triangular system a block of 64 rows and runs a separate decode step;
this file knows neither, so it shares no line with
``models/gated_delta.py``).  The equations, for hidden 2048, RMSNorm of
eps 1e-6 computed ``x_hat * (1 + w)`` (the family's zero-centred scale),
no bias anywhere:

* ``x = E[token]``; head: final RMSNorm, ``logits = h W_out`` (untied);
* layer l is FULL attention where ``(l + 1) % full_attention_interval
  == 0`` (4), else a GATED DELTA layer; every layer
  ``x = x + mixer(N_in(x))``, then ``x = x + moe(N_post(x))``;
* the full layer (16 query and 2 key-value heads of 256): ``[q | gate]
  = u Wq``, a head's 256 query and 256 gate columns side by side; ``k``,
  ``v``; ``q`` and ``k`` RMSNorm'd a head by itself under ``(1 + w)``;
  the FIRST 64 of a head's 256 dimensions rotate (``partial_rotary_factor``
  0.25) on halves ``(i, i + 32)`` at theta 1e7, the other 192 pass;
  ``o = softmax(q k^T / 16, causal) v``, 8 query heads a key-value head;
  ``x += (o * sigmoid(gate)) Wo``;
* the gated delta layer (16 key heads and 32 value heads of 128, four
  taps): ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``; ``[q | k |
  v] = silu(conv_4([q | k | v]))`` over each of the 8,192 channels, zeros
  before the sequence; ``q = l2norm(q) / sqrt(128)``, ``k = l2norm(k)``
  over a head; key head j serves value heads 2j and 2j + 1; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; a value
  head's state ``S`` [128 keys, 128 values], one token at a time:

      S'  = exp(g_t) S_{t-1}
      d_t = beta_t (v_t - S'^T k_t)
      S_t = S' + k_t d_t^T
      o_t = S_t^T q_t

  ``y = RMSNorm_w(o) * silu(z)`` over a head's 128 under the scale ``w``
  (NOT ``1 + w``), ``x += y W_out``;
* the experts: ``p = softmax(h W_r)`` over all the router's outputs in
  float32, the ten largest divided by their sum; expert e ``(silu(u[:512])
  * u[512:]) W_out,e`` with ``u = h W_in,e``; the shared expert the same
  form, every token, times ``sigmoid(h w_s)``.

DEPARTURES from the published code, each with its reason: (a) the
published delta layer computes its recurrence by a chunked algorithm of
block 64 (``torch_chunk_gated_delta_rule``) or a fused kernel; the
recurrence here (its ``torch_recurrent_gated_delta_rule``) is what both
compute; (b) the published projections ``in_proj_qkvz`` / ``in_proj_ba``
lay their columns out a KEY HEAD at a time ([q | k | v v | z z] of head
j, then head j + 1); here they are [q | k | v | z] and [b | a] whole,
a fixed permutation of columns a checkpoint's conversion carries; (c)
the l2 norm is ``x * rsqrt(sum x^2 + 1e-6)``, the published kernels';
(d) which half of ``u`` the ``silu`` takes is a convention under random
weights: the first (``gate_proj``); (e) ONE CHIP'S SHARE of the experts:
the router scores all its outputs and only the experts ``weights`` holds
(``cfg['num_experts']`` of them from ``cfg['experts_first']`` on) are
computed, under the gates the router gave over all ten choices: what
the chip of the deployment computes before the exchange (``held=None``
computes every expert of the share); (f) no multi-token-prediction
module: the published config has no key of one.

``router_margins``, ``routing`` and ``forced`` count every layer (all
are sparse).  A margin, and how far a given expert lies below the last
chosen one, are in router LOGITS.

``faults`` (a set of names) turns this file into a FAULTY reference, for
the readings the probe's limits rest on and for the tests' controls:
``no_delta`` (the ``S'^T k`` term left out: a plain additive state),
``no_beta`` (``beta`` 1), ``no_decay`` (``g`` 0), ``decay_after``
(``d_t`` formed from ``S_{t-1}`` before it is decayed), ``no_l2norm``,
``no_q_scale`` (``1 / sqrt(128)`` left out), ``kv_neighbour`` (value
head i reading key head ``i mod 16``: 2j + 1 reads 2j + 1),
``state_dropped_at_chunks`` / ``conv_dropped_at_chunks`` (``S``, or the
convolution's columns, start from zeros every 512 tokens, or
``cfg['fault_chunk']``), ``no_z_gate`` (``silu(z)`` left out),
``norm_after_gate`` (``RMSNorm(o * silu(z))``), ``state_bf16`` (``S``
rounded to bf16 after every token), ``full_rotary`` (the whole head
rotated), ``no_attn_gate``, ``no_shared_gate``, ``scale_is_w`` (every
``(1 + w)`` read as ``w``: the norms scale by ``w`` alone),
``nine_experts`` (the
lowest of the ten chosen dropped), ``float8`` (weights and each layer's
normed inputs rounded to e4m3, the nearest precision below the stated
bf16), ``bf16`` (the stated precision itself).
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
L2_EPS = 1e-6
FAULTS = ("no_delta", "no_beta", "no_decay", "decay_after", "no_l2norm",
          "no_q_scale", "kv_neighbour", "state_dropped_at_chunks",
          "conv_dropped_at_chunks", "no_z_gate", "norm_after_gate",
          "state_bf16", "full_rotary", "no_attn_gate", "no_shared_gate",
          "scale_is_w", "nine_experts", "float8", "bf16")
PRECISION = frozenset({"float8", "bf16"})
DELTA = frozenset({"no_delta", "no_beta", "no_decay", "decay_after",
                   "no_l2norm", "no_q_scale", "kv_neighbour",
                   "state_dropped_at_chunks", "conv_dropped_at_chunks",
                   "no_z_gate", "norm_after_gate", "state_bf16",
                   "scale_is_w"})


def rms_norm(x, w, eps, faults=frozenset()):
    """``x_hat * (1 + w)``: the family's zero-centred scale."""
    scale = w if "scale_is_w" in faults else 1.0 + w
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rounded(x, faults):
    """The precision faults: x as the named precision holds it."""
    if "float8" in faults:
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if "bf16" in faults:
        # reduce_precision: an astype there and back is dropped by a
        # compiler that is allowed excess precision
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _rotate_half(x, positions, theta: float, rot: int):
    """x [s, heads, d] with its first ``rot`` dimensions rotated on halves
    ``(i, i + rot / 2)`` and the rest passed."""
    freq = theta ** (-2.0 * jnp.arange(rot // 2, dtype=jnp.float32) / rot)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    part = x[..., :rot]
    turned = jnp.concatenate([-part[..., rot // 2:], part[..., :rot // 2]],
                             axis=-1)
    return jnp.concatenate([part * cos + turned * sin, x[..., rot:]],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "eps", "theta", "rot", "faults"))
def attention_inputs(x, w, *, n_heads, n_kv, eps, theta, rot, faults):
    """q, gate [s, heads, d], k, v [s, kv heads, d] of one full layer for
    one sequence x [s, h]."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        precision = faults & PRECISION
        w = {k: _rounded(v, precision) for k, v in w.items()}
        hn = _rounded(rms_norm(x, w["mixer_norm"], eps, faults), precision)
        d = w["wk"].shape[1] // n_kv
        qg = (hn @ w["wq"]).reshape(s, n_heads, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = (hn @ w["wk"]).reshape(s, n_kv, d)
        v = (hn @ w["wv"]).reshape(s, n_kv, d)
        q = rms_norm(q, w["q_norm"], eps, faults)
        k = rms_norm(k, w["k_norm"], eps, faults)
        pos = jnp.arange(s)
        rot = d if "full_rotary" in faults else rot
        return (_rotate_half(q, pos, theta, rot), gate,
                _rotate_half(k, pos, theta, rot), v)


@functools.partial(jax.jit, static_argnames=("scale",))
def attend_block(q, k, v, first, *, scale):
    """A block of queries [bq, heads, d] at positions ``first ..`` over
    the whole sequence's keys, causal.  Returns [bq, heads, d]."""
    with jax.default_matmul_precision(HIGHEST):
        bq, n_heads, d = q.shape
        rep = n_heads // k.shape[1]
        seen = (jnp.arange(k.shape[0])[None, :]
                <= (first + jnp.arange(bq))[:, None])
        scores = jnp.einsum("qhd,khd->hqk", q,
                            jnp.repeat(k, rep, axis=1)) * scale
        scores = jnp.where(seen[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, jnp.repeat(v, rep, axis=1))


def attention_out(x, w, cfg, faults=frozenset()):
    """x [s, h] -> what a full layer's mixer gives [s, h], a block of
    queries at a time."""
    d = int(cfg["head_dim"])
    names = ("mixer_norm", "wq", "wk", "wv", "q_norm", "k_norm")
    q, gate, k, v = attention_inputs(
        x, {n: w[n] for n in names},
        n_heads=int(cfg["num_attention_heads"]),
        n_kv=int(cfg["num_key_value_heads"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        rot=int(d * float(cfg["partial_rotary_factor"])),
        faults=faults & (PRECISION | {"full_rotary", "scale_is_w"}))
    out = jnp.concatenate(
        [attend_block(q[first:first + QUERY_BLOCK], k, v, first,
                      scale=1.0 / math.sqrt(d))
         for first in range(0, x.shape[0], QUERY_BLOCK)])
    with jax.default_matmul_precision(HIGHEST):
        if "no_attn_gate" not in faults:
            out = out * jax.nn.sigmoid(gate)
        return out.reshape(x.shape[0], -1) @ _rounded(w["wo"],
                                                      faults & PRECISION)


@functools.partial(jax.jit, static_argnames=(
    "kh", "hv", "dk", "dv", "taps", "eps", "faults", "chunk"))
def delta_out(x, w, *, kh, hv, dk, dv, taps, eps, faults, chunk=CHUNK):
    """x [s, h] -> (what a gated delta layer's mixer gives [s, h], the
    state ``S`` [value heads, d_key, d_value] its last token leaves, the
    last ``taps - 1`` columns of [q | k | v] before the convolution): the
    recurrence one token at a time."""
    with jax.default_matmul_precision(HIGHEST):
        s = x.shape[0]
        precision = faults & PRECISION
        w = {k: _rounded(v, precision) for k, v in w.items()}
        hn = _rounded(rms_norm(x, w["mixer_norm"], eps, faults), precision)
        cdim = 2 * kh * dk + hv * dv
        qkvz = hn @ w["in_proj"]
        qkv, z = qkvz[:, :cdim], qkvz[:, cdim:]
        ba = hn @ w["ba_proj"]
        b, a = ba[:, :hv], ba[:, hv:]
        # the taps lie over the taps - 1 columns before a token and its own
        t = jnp.arange(s)
        fresh = ((t % chunk)[:, None] + jnp.arange(taps)[None, :]
                 < taps - 1)                        # [s, K]: before a chunk
        ext = jnp.concatenate([jnp.zeros((taps - 1, cdim)), qkv])
        acc = jnp.zeros_like(qkv)
        for j in range(taps):
            col = ext[j:j + s]
            if "conv_dropped_at_chunks" in faults:
                col = jnp.where(fresh[:, j:j + 1], 0.0, col)
            acc = acc + col * w["conv_kernel"][:, j]
        mixed = jax.nn.silu(acc)
        q = mixed[:, :kh * dk].reshape(s, kh, dk)
        k = mixed[:, kh * dk:2 * kh * dk].reshape(s, kh, dk)
        v = mixed[:, 2 * kh * dk:].reshape(s, hv, dv)
        if "no_l2norm" not in faults:
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        if "no_q_scale" not in faults:
            q = q / math.sqrt(dk)
        r = hv // kh
        if "kv_neighbour" in faults:
            q, k = jnp.tile(q, (1, r, 1)), jnp.tile(k, (1, r, 1))
        else:
            q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
        beta = (jnp.ones_like(b) if "no_beta" in faults
                else jax.nn.sigmoid(b))
        g = (jnp.zeros_like(a) if "no_decay" in faults
             else -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"]))

        def step(S, inp):
            q_t, k_t, v_t, g_t, b_t, first = inp
            if "state_dropped_at_chunks" in faults:
                S = jnp.where(first, 0.0, S)
            decayed = jnp.exp(g_t)[:, None, None] * S
            read = S if "decay_after" in faults else decayed
            answered = (jnp.zeros_like(v_t) if "no_delta" in faults
                        else jnp.einsum("hkv,hk->hv", read, k_t))
            d_t = b_t[:, None] * (v_t - answered)
            S = decayed + k_t[:, :, None] * d_t[:, None, :]
            if "state_bf16" in faults:
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
            return S, jnp.einsum("hkv,hk->hv", S, q_t)

        last, o = jax.lax.scan(
            step, jnp.zeros((hv, dk, dv), jnp.float32),
            (q, k, v, g, beta, t % chunk == 0))

        def head_norm(y):
            # the gated norm's scale is w itself, not 1 + w
            return y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + eps) * w["gate_norm"]

        zg = (jnp.ones_like(z) if "no_z_gate" in faults
              else jax.nn.silu(z)).reshape(s, hv, dv)
        y = (head_norm(o * zg) if "norm_after_gate" in faults
             else head_norm(o) * zg)
        return (y.reshape(s, hv * dv) @ w["out_proj"], last,
                qkv[s - (taps - 1):])


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "faults"))
def moe_gates(x, ffn_norm, gate, forced, *, eps, top_k, faults=frozenset()):
    """Normed input; for every token and expert of the ROUTER's the
    weight that expert gets (the softmax over all the router's outputs,
    the token's top_k divided by their sum, zero elsewhere); the router's
    margin (the last chosen logit minus the first rejected one's); the
    experts chosen [s, top_k]; and how far below the last chosen logit
    the lowest of them lies.  A row of ``forced`` [s, top_k] that is not
    negative is taken for the token's experts as it stands (the gates
    still this router's own scores of them)."""
    with jax.default_matmul_precision(HIGHEST):
        hn = rms_norm(x, ffn_norm, eps, faults)
        logits = hn @ gate                                  # [s, R]
        top, idx = jax.lax.top_k(logits, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        idx = jnp.where(forced[:, :1] >= 0, forced, idx[:, :top_k])
        at = jnp.take_along_axis(logits, idx, axis=1)
        below = top[:, top_k - 1] - jnp.min(at, axis=1)
        if "nine_experts" in faults:
            at = jnp.where(at == jnp.min(at, axis=1, keepdims=True),
                           -jnp.inf, at)
        # softmax over all, the chosen ones over their sum: the softmax
        # over the chosen logits
        dense = jnp.zeros_like(logits)
        dense = dense.at[jnp.arange(x.shape[0])[:, None], idx].set(
            jax.nn.softmax(at, axis=-1))
        return hn, dense, margin, idx, jnp.maximum(below, 0.0)


@functools.partial(jax.jit, static_argnames=("faults",))
def expert_out(hn, gate_weight, w1, w2, w3, *, faults=frozenset()):
    """One expert (or, with the shared gate for a weight, the shared
    expert) over every token, weighted by its gate: ``w1`` is the first
    half of ``W_in`` (under the silu), ``w3`` the second."""
    with jax.default_matmul_precision(HIGHEST):
        precision = faults & PRECISION
        hn, w1, w2, w3 = (_rounded(a, precision) for a in (hn, w1, w2, w3))
        y = (jax.nn.silu(hn @ w1) * (hn @ w3)) @ w2
        return y * gate_weight[:, None]


@functools.partial(jax.jit, static_argnames=("eps", "faults"))
def head_block(x, norm, output_rows, *, eps, faults=frozenset()):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm, eps, faults) @ output_rows.T


def held_experts(cfg: dict, routed: int) -> range:
    """The router's experts this share of the layer computes."""
    first = int(cfg.get("experts_first", 0))
    return range(first, min(first + int(cfg["num_experts"]), routed))


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
        x, w["ffn_norm"], w["gate"], jnp.asarray(given),
        eps=float(cfg["rms_norm_eps"]), top_k=top_k,
        faults=faults & {"nine_experts", "scale_is_w"})
    routed = w["gate"].shape[1]
    own = faults & PRECISION
    y = jnp.zeros_like(x)
    for e in (held_experts(cfg, routed) if held is None else held):
        ew = weights.expert(i, e)
        y = y + expert_out(hn, dense[:, e], ew["w1"], ew["w2"], ew["w3"],
                           faults=own)
    if shared:
        with jax.default_matmul_precision(HIGHEST):
            open_ = (jnp.ones((s,), jnp.float32)
                     if "no_shared_gate" in faults
                     else jax.nn.sigmoid(hn @ w["shared_gate"])[:, 0])
        y = y + expert_out(hn, open_, w["shared_w1"], w["shared_w2"],
                           w["shared_w3"], faults=own)
    return y, margin, chose, below


def layer_is_full(cfg: dict, i: int) -> bool:
    return (i + 1) % int(cfg["full_attention_interval"]) == 0


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None, rows=None, faults=frozenset(),
                   routing: list = None, forced: dict = None,
                   states: list = None, tails: list = None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32), or [len(rows), vocab]
    at the positions ``rows``.  With a list for ``router_margins`` each
    layer appends its margins [s].  With a list for ``routing`` each
    layer appends (the experts chosen [s, top_k], how far below its own
    last choice the lowest of them lies [s]); ``forced`` maps a layer's
    index to {position: experts}: the experts that token is given there,
    whatever this router would choose.  With a list for ``states`` each
    delta layer appends the state its last token leaves, with one for
    ``tails`` the columns its convolution would carry on.  ``turned`` is
    the probe's other way of saying so and is not implemented here."""
    if turned:
        raise NotImplementedError("give the experts (forced), not a turn")
    tokens = np.asarray(tokens, np.int32)
    faults = frozenset(faults)
    assert faults <= set(FAULTS), faults
    eps = float(cfg["rms_norm_eps"])
    x = weights.embedding_rows(tokens)
    delta = dict(kh=int(cfg["linear_num_key_heads"]),
                 hv=int(cfg["linear_num_value_heads"]),
                 dk=int(cfg["linear_key_head_dim"]),
                 dv=int(cfg["linear_value_head_dim"]),
                 taps=int(cfg["linear_conv_kernel_dim"]), eps=eps,
                 chunk=int(cfg.get("fault_chunk", CHUNK)))
    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        if layer_is_full(cfg, i):
            mixed = attention_out(x, w, cfg, faults)
        else:
            names = ("mixer_norm", "in_proj", "ba_proj", "conv_kernel",
                     "dt_bias", "A_log", "gate_norm", "out_proj")
            mixed, last, tail = delta_out(
                x, {n: w[n] for n in names}, **delta,
                faults=faults & (PRECISION | DELTA))
            if states is not None:
                states.append(last)
            if tails is not None:
                tails.append(tail)
        x = x + mixed
        y, margin, chose, below = moe_out(
            x, w, weights, cfg, i, (forced or {}).get(i, {}), faults)
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
                                                         vocab)), eps=eps,
                    faults=faults & {"scale_is_w"})
         for v0 in range(0, vocab, VOCAB_BLOCK)], axis=-1)


def position_losses(logits, labels) -> jax.Array:
    """Cross entropy at every position [s] (float32)."""
    labels = jnp.asarray(np.asarray(labels, np.int32))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def cross_entropy(logits, labels) -> jax.Array:
    """Summed cross entropy over positions (float32)."""
    return jnp.sum(position_losses(logits, labels))
