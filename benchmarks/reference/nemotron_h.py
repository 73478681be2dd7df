"""Plain reference: the NVIDIA-Nemotron-3-Nano-30B-A3B forward pass
(``model_type`` ``nemotron_h``,
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s ``config.json``).

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no
batching, no chunk; one sequence at a time, one expert at a time, the
state-space recurrence as a plain ``lax.scan`` OVER TOKENS (the program
runs a chunked scan over blocks of 128 and a separate decode step; this
file knows neither, so it is independent of ``models/mamba.py``, and it
routes and multiplies an expert at a time, independent of
``models/moe.py``).  The equations, for hidden 2688, RMSNorm with eps
1e-5 (``layer_norm_epsilon``), no bias but the convolution's, by the
config's own keys:

* the stack: ``x_0 = E[token]``; layer l of kind ``k_l`` (the l-th letter
  of ``hybrid_override_pattern``) is ONE sublayer under ONE norm,
  ``x_{l+1} = x_l + f_{k_l}(RMSNorm_l(x_l))``; ``logits =
  RMSNorm_f(x_L) W_head``, ``W_head`` its own matrix
  (``tie_word_embeddings`` false).  No multiplier anywhere;
* ``M``, the Mamba-2 mixer (``mamba_num_heads`` 64 heads of
  ``mamba_head_dim`` 64, ``ssm_state_size`` 128, ``n_groups`` 8,
  ``conv_kernel`` 4):
  1. ``[z | xBC | dt] = u W_in``: 4096 | 6144 | 64;
  2. ``xBC_t = silu(b + sum_j w[:, j] xBC_{t-3+j})`` over each of the
     6,144 channels (zeros before the sequence), then ``x_t`` [64, 64],
     ``B_t``, ``C_t`` [8, 128]: head i uses group ``i // 8``;
  3. ``delta_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``;
  4. ``S_t = exp(delta_t A) S_{t-1} + delta_t (x_t outer B_t)``,
     ``y_t = S_t C_t + D x_t``;
  5. THE GATED NORM BY GROUP: ``v = y * silu(z)``, each of the 8 groups
     of 512 channels ``v_g / sqrt(mean(v_g^2) + eps)``, times a weight
     of 4096; ``v W_out``;
* ``*``, attention: 32 query heads of ``head_dim`` 128 against 2
  key/value heads (query head i against ``i // 16``), causal softmax of
  ``q k^T / sqrt(128)``, NO position embedding: the published
  ``NemotronHAttention`` applies none (``rope_theta`` and
  ``partial_rotary_factor`` stand in the config and rotate nothing);
* ``E``, the expert layer: ``s = sigmoid(u W_g)`` in float32 over all
  the router's experts (128); the ``num_experts_per_tok`` (6) chosen are
  the largest of ``s + b`` (``b`` the choice bias; ``n_group`` 1 and
  ``topk_group`` 1: a plain top-6); gates ``routed_scaling_factor x s_e
  / (sum of the chosen s + 1e-20)`` (``norm_topk_prob``; ``b`` is not in
  the gates); ``out = sum_e g_e relu(u W_up,e)^2 W_down,e + relu(u
  W_up,s)^2 W_down,s``: UNGATED, two matrices an expert and the shared
  MLP alike.

DEPARTURES from the published code, each with its reason: (a) the
published mixer computes step 4 by a chunked algorithm (``chunk_size``
128) or a fused kernel; the recurrence here is what both compute;
(b) ``expand``, ``time_step_*`` and ``rescale_prenorm_residual`` shape
the published INITIALISATION only and are not read; (c) ONE CHIP'S SHARE
of the experts: the router scores all 128 and only the experts
``weights`` holds (``cfg['n_routed_experts']`` of them from
``cfg['experts_first']`` on) are computed, under the gates the router
gave over all six choices: what the chip of the deployment computes
before the exchange (``held=None``; ``held=range(R)`` with weights that
hold them all is the uncut layer).

``router_margins``, ``routing`` and ``forced`` count the EXPERT layers
(the ``E`` of the pattern, in order).  A margin, and how far a given
expert lies below the last chosen one, are in the choice's own scale
(``s + b``).

``faults`` (a set of names) turns this file into a FAULTY reference, for
the readings the probe's limits rest on and for the tests' controls:
``norm_whole`` (the gated norm over the whole inner width), ``group_zero``
(``B`` / ``C`` of group 0 given to every head), ``expert_swiglu`` (an
expert and the shared MLP SwiGLU-shaped: ``silu`` of the first half of
``u`` times its second half, over the first half of ``W_down``'s rows),
``expert_relu`` (``relu`` with no square), ``bias_in_gates`` (the choice
bias added to the gates), ``no_scale`` (``routed_scaling_factor`` left
out), ``rope_on`` (queries and keys rotated at ``rope_theta``),
``no_shared``, ``second_norm`` (a mixer layer's output normed again by
the layer's norm before the residual: a second norm a layer), ``no_D``,
``gate_after_norm``, ``no_conv_bias``, ``state_bf16`` (``S`` rounded to
bf16 after every token), ``state_dropped_at_chunks`` (``S`` and the
convolution's columns start from zeros every 512 tokens, or
``cfg['fault_chunk']``), ``float8`` (weights and each layer's normed
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
CHUNK = 512
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}
FAULTS = ("norm_whole", "group_zero", "expert_swiglu", "expert_relu",
          "bias_in_gates", "no_scale", "rope_on", "no_shared",
          "second_norm", "no_D", "gate_after_norm", "no_conv_bias",
          "state_bf16", "state_dropped_at_chunks", "float8", "bf16")
PRECISION = frozenset({"float8", "bf16"})
MAMBA_FAULTS = frozenset({"norm_whole", "group_zero", "no_D",
                          "gate_after_norm", "no_conv_bias", "state_bf16",
                          "state_dropped_at_chunks"})
EXPERT_FAULTS = frozenset({"expert_swiglu", "expert_relu"})


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def group_rms_norm(x, w, eps, groups: int):
    """x [s, d] normed over each of ``groups`` runs of d / groups
    channels apart, then the weight of the whole width."""
    s, d = x.shape
    g = x.reshape(s, groups, d // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(s, d) * w


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


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta",
                                             "faults"))
def attention_inputs(hn, w, *, n_heads, n_kv, theta, faults):
    """q [s, heads, d], k, v [s, kv heads, d] of one attention layer for
    one sequence's normed input hn [s, h]: nothing rotates."""
    with jax.default_matmul_precision(HIGHEST):
        s = hn.shape[0]
        w = {k: _rounded(v, faults) for k, v in w.items()}
        hn = _rounded(hn, faults)
        d = w["wq"].shape[1] // n_heads
        q = (hn @ w["wq"]).reshape(s, n_heads, d)
        k = (hn @ w["wk"]).reshape(s, n_kv, d)
        v = (hn @ w["wv"]).reshape(s, n_kv, d)
        if "rope_on" in faults:
            pos = jnp.arange(s)
            q, k = _rotate_half(q, pos, theta), _rotate_half(k, pos, theta)
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
    q, k, v = attention_inputs(
        hn, {n: w[n] for n in ("wq", "wk", "wv")},
        n_heads=int(cfg["num_attention_heads"]),
        n_kv=int(cfg["num_key_value_heads"]),
        theta=float(cfg.get("rope_theta", 10000.0)),
        faults=faults & (PRECISION | {"rope_on"}))
    scale = 1.0 / math.sqrt(q.shape[-1])
    out = [attend_block(q[first:first + QUERY_BLOCK], k, v, first,
                        scale=scale)
           for first in range(0, hn.shape[0], QUERY_BLOCK)]
    with jax.default_matmul_precision(HIGHEST):
        return jnp.concatenate(out) @ _rounded(w["wo"], faults & PRECISION)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "d_head", "d_state", "n_groups", "d_conv", "eps", "faults",
    "chunk"))
def mamba_out(hn, w, *, n_heads, d_head, d_state, n_groups, d_conv, eps,
              faults, chunk=CHUNK):
    """The normed input hn [s, h] -> (what a Mamba-2 layer's mixer gives
    [s, h], the state ``S`` [heads, d_head, d_state] its last token
    leaves): steps 1-5 of the module docstring, the recurrence one token
    at a time."""
    with jax.default_matmul_precision(HIGHEST):
        s = hn.shape[0]
        precision = faults & PRECISION
        w = {k: _rounded(v, precision) for k, v in w.items()}
        hn = _rounded(hn, precision)
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
        B = xBC[:, di:di + gs].reshape(s, n_groups, d_state)
        C = xBC[:, di + gs:].reshape(s, n_groups, d_state)
        if "group_zero" in faults:
            B, C = (jnp.repeat(a[:, :1], n_groups, axis=1) for a in (B, C))
        # head i uses group i // rep
        B, C = jnp.repeat(B, rep, axis=1), jnp.repeat(C, rep, axis=1)
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
                # excess precision drops that round trip (the TPU's does)
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
            return S, jnp.einsum("hdn,hn->hd", S, C_t)

        last, y = jax.lax.scan(
            step, jnp.zeros((n_heads, d_head, d_state), jnp.float32),
            (xs, B, C, delta, t % chunk == 0))
        if "no_D" not in faults:
            y = y + w["D"][:, None] * xs
        y = y.reshape(s, di)
        groups = 1 if "norm_whole" in faults else n_groups
        if "gate_after_norm" in faults:
            y = group_rms_norm(y, w["gate_norm"], eps, groups) * jax.nn.silu(z)
        else:
            y = group_rms_norm(y * jax.nn.silu(z), w["gate_norm"], eps,
                               groups)
        return y @ w["out_proj"], last


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "faults"))
def moe_gates(hn, gate, bias, forced, *, top_k, scale, faults):
    """For every token and expert of the ROUTER's the weight that expert
    gets (``scale`` times the token's chosen sigmoid scores over their
    sum, zero elsewhere); the router's margin (the last chosen ``s + b``
    minus the first rejected one's); the experts chosen [s, top_k]; and
    how far below the last chosen ``s + b`` the lowest of them lies.  A
    row of ``forced`` [s, top_k] that is not negative is taken for the
    token's experts as it stands (the gates still this router's own
    scores of them)."""
    with jax.default_matmul_precision(HIGHEST):
        scores = jax.nn.sigmoid(hn @ gate)                  # [s, R]
        choice = scores + bias
        top, idx = jax.lax.top_k(choice, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        idx = jnp.where(forced[:, :1] >= 0, forced, idx[:, :top_k])
        below = top[:, top_k - 1] - jnp.min(
            jnp.take_along_axis(choice, idx, axis=1), axis=1)
        at = jnp.take_along_axis(
            choice if "bias_in_gates" in faults else scores, idx, axis=1)
        at = at / (jnp.sum(at, axis=-1, keepdims=True) + 1e-20)
        if "no_scale" not in faults:
            at = at * scale
        dense = jnp.zeros_like(scores)
        dense = dense.at[jnp.arange(hn.shape[0])[:, None], idx].set(at)
        return dense, margin, idx, jnp.maximum(below, 0.0)


@functools.partial(jax.jit, static_argnames=("faults",))
def expert_out(hn, gate_weight, w_up, w_down, *, faults=frozenset()):
    """One expert (or, with a weight of ones, the shared MLP) over every
    token, weighted by its gate: ``relu(hn W_up)^2 W_down``."""
    with jax.default_matmul_precision(HIGHEST):
        precision = faults & PRECISION
        hn, w_up, w_down = (_rounded(a, precision)
                            for a in (hn, w_up, w_down))
        u = hn @ w_up
        if "expert_swiglu" in faults:
            f = u.shape[1] // 2
            y = (jax.nn.silu(u[:, :f]) * u[:, f:]) @ w_down[:f]
        elif "expert_relu" in faults:
            y = jax.nn.relu(u) @ w_down
        else:
            y = jnp.square(jax.nn.relu(u)) @ w_down
        return y * gate_weight[:, None]


@functools.partial(jax.jit, static_argnames=("eps",))
def normed(x, w, *, eps):
    return rms_norm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_block(x, norm, output_rows, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm, eps) @ output_rows.T


def held_experts(cfg: dict, routed: int) -> range:
    """The router's experts this share of the layer computes."""
    first = int(cfg.get("experts_first", 0))
    return range(first, min(first + int(cfg["n_routed_experts"]), routed))


def moe_out(hn, w, weights, cfg, i: int, forced_rows, faults, held=None):
    """The normed input hn [s, h] -> (what layer i's experts and shared
    MLP give [s, h], margins, chosen, below).  ``held``: the router's
    experts computed (None: ``held_experts``); the shared MLP once."""
    s = hn.shape[0]
    top_k = int(cfg["num_experts_per_tok"])
    given = np.full((s, top_k), -1, np.int32)
    for t, experts in forced_rows.items():
        given[t] = experts
    precision = faults & PRECISION
    dense, margin, chose, below = moe_gates(
        hn, w["gate"], w["choice_bias"], jnp.asarray(given), top_k=top_k,
        scale=float(cfg["routed_scaling_factor"]),
        faults=faults & {"bias_in_gates", "no_scale"})
    routed = w["gate"].shape[1]
    own = faults & (PRECISION | EXPERT_FAULTS)
    hn = _rounded(hn, precision)
    y = jnp.zeros_like(hn)
    for e in (held_experts(cfg, routed) if held is None else held):
        ew = weights.expert(i, e)
        y = y + expert_out(hn, dense[:, e], ew["w_up"], ew["w_down"],
                           faults=own)
    if "no_shared" not in faults:
        y = y + expert_out(hn, jnp.ones((s,), jnp.float32), w["shared_up"],
                           w["shared_down"], faults=own)
    return y, margin, chose, below


def kinds_of(cfg: dict) -> list:
    """The kind of each layer as run: the first ``num_hidden_layers``
    letters of ``hybrid_override_pattern``."""
    pattern = cfg["hybrid_override_pattern"]
    return [KINDS[c] for c in pattern[:int(cfg["num_hidden_layers"])]]


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None, rows=None, faults=frozenset(),
                   routing: list = None, forced: dict = None,
                   states: list = None, held=None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32), or [len(rows), vocab]
    at the positions ``rows``.  With a list for ``router_margins`` each
    EXPERT layer appends its margins [s].  With a list for ``routing``
    each expert layer appends (the experts chosen [s, top_k], how far
    below its own last choice the lowest of them lies [s]); ``forced``
    maps an expert layer's index AMONG THE EXPERT LAYERS to {position:
    experts}: the experts that token is given there, whatever this
    router would choose.  With a list for ``states`` each state-space
    layer appends the state its last token leaves.  ``held``: the
    router's experts computed in every expert layer (None: the share
    ``cfg`` states).  ``turned`` is the probe's other way of saying so
    and is not implemented here."""
    if turned:
        raise NotImplementedError("give the experts (forced), not a turn")
    tokens = np.asarray(tokens, np.int32)
    faults = frozenset(faults)
    assert faults <= set(FAULTS), faults
    eps = float(cfg.get("layer_norm_epsilon", cfg.get("rms_norm_eps")))
    x = weights.embedding_rows(tokens)
    mamba = dict(n_heads=int(cfg["mamba_num_heads"]),
                 d_head=int(cfg["mamba_head_dim"]),
                 d_state=int(cfg["ssm_state_size"]),
                 n_groups=int(cfg["n_groups"]),
                 d_conv=int(cfg["conv_kernel"]), eps=eps,
                 chunk=int(cfg.get("fault_chunk", CHUNK)))
    sparse = 0
    for i, kind in enumerate(kinds_of(cfg)):
        w = weights.layer(i)
        hn = normed(x, w["norm"], eps=eps)
        if kind == "moe":
            y, margin, chose, below = moe_out(
                hn, w, weights, cfg, i, (forced or {}).get(sparse, {}),
                faults, held)
            if router_margins is not None:
                router_margins.append(margin)
            if routing is not None:
                routing.append((np.asarray(chose), np.asarray(below)))
            sparse += 1
        elif kind == "mamba":
            names = ("in_proj", "conv_kernel", "conv_bias", "dt_bias",
                     "A_log", "D", "gate_norm", "out_proj")
            y, last = mamba_out(hn, {n: w[n] for n in names}, **mamba,
                                faults=faults & (PRECISION | MAMBA_FAULTS))
            if states is not None:
                states.append(last)
        else:
            y = attention_out(hn, w, cfg, faults)
        if "second_norm" in faults and kind != "moe":
            y = normed(y, w["norm"], eps=eps)
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
