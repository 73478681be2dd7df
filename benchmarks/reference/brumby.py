"""A plain decoder for Brumby-14B-Base (Manifest AI, ``model_type``
``brumby``): the forward pass in ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, no cache, no kernel, no
batching, IN THE QUADRATIC FORM.  It shares no line with the program's
``phi``, state or chunking: a token's mixer output is written as the sum
over the tokens before it that the published description gives,

    a_t,g = logsigmoid(u_t W_g)
    w_tj  = exp(A_t,g - A_j,g) (q_t,h . k_j,g)^2        j <= t,  A the
                                                        running sum of a
    o_t,h = sum_j w_tj v_j,g / sum_j w_tj

computed a key-value head at a time in blocks of ``QUERY_BLOCK`` query
positions against every key before them (the decay from DIFFERENCES of
the running sum, one division), so that 8 layers at the published widths
fit beside the serving engine on one chip; the MLP in column blocks for
the same reason.  The trunk is Qwen3's: RMSNorm eps 1e-6, no bias, each
query and key head's 128 values normed by themselves under one scale of
128, rotary on halves (i, i + d/2) at theta 1e6, a SwiGLU MLP, an untied
head under the final norm.

Departures from the published description: none in the mathematics as
``benchmarks/configs/brumby-14b-serve.json`` states it under ``assumed``
(the degree 2, the gate's form and that it is a key-value head's, the
normaliser, the per-head norms and the rotary's form are NOT in the
published config's keys and are assumed there, each with its basis); no
scale on ``q . k`` (it cancels in the quotient).

``state_of`` gives what the PROGRAM's recurrent state must hold after
the sequence, from this file's own ``k``, ``v`` and ``a`` of one layer:
``S = sum_j exp(A_T - A_j) phi(k_j) v_j^T`` and ``z`` likewise, ``phi``
in the program's stated layout (``phi_rotations``: rotation o of a head
is ``c_o x[a] x[(a + o) mod d]``), which is the one thing here that
follows the program, because a state can only be compared in a layout.

``faults`` plants the named departures the probe's tolerances must tell
(``brumby_controls.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
QUERY_BLOCK = 512
MLP_BLOCK = 4352
VOCAB_BLOCK = 16384
CHUNK = 512
FAULTS = ("degree_one", "no_normaliser", "no_sqrt2", "no_gate",
          "own_term_decayed", "sum_not_decayed", "state_dropped_at_chunks",
          "kv_neighbour", "no_rope", "no_qk_norm", "bf16", "float8")
PRECISION = frozenset({"float8", "bf16"})


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, positions, theta: float):
    """x [s, heads, d] rotated on halves (i, i + d/2) at ``positions``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _rounded(x, faults):
    """x as a lower precision holds it (a planted fault), in float32."""
    if "float8" in faults:
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    if "bf16" in faults:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta",
                                             "eps", "faults"))
def mixer_inputs(hn, w, *, n_heads, n_kv, theta, eps, faults):
    """The normed input hn [s, h] -> q [s, heads, d], k, v [s, kv, d]
    and the log-gates a [s, kv]."""
    s = hn.shape[0]
    with jax.default_matmul_precision(HIGHEST):
        q = (hn @ w["wq"]).reshape(s, n_heads, -1)
        k = (hn @ w["wk"]).reshape(s, n_kv, -1)
        v = (hn @ w["wv"]).reshape(s, n_kv, -1)
        a = jax.nn.log_sigmoid(hn @ w["wg"])
    if "no_qk_norm" not in faults:
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    if "no_rope" not in faults:
        pos = jnp.arange(s)
        q, k = rotary(q, pos, theta), rotary(k, pos, theta)
    if "no_gate" in faults:
        a = jnp.zeros_like(a)
    return (_rounded(q, faults), _rounded(k, faults), _rounded(v, faults),
            a)


@functools.partial(jax.jit, static_argnames=("faults", "chunk"))
def retain_block(q, k, v, A, a, first, *, faults, chunk):
    """One key-value head: queries q [r, m, d] at positions ``first ..``
    against keys k and values v [s, d] (all of them; the mask cuts), the
    running sum A [s] of the log-gates a [s] -> [r, m, d]."""
    m, s = q.shape[1], k.shape[0]
    t = first + jnp.arange(m)
    j = jnp.arange(s)
    seen = j[None, :] <= t[:, None]
    if "state_dropped_at_chunks" in faults:
        # what stood before the query's chunk is forgotten
        seen &= j[None, :] >= (t[:, None] // chunk) * chunk
    with jax.default_matmul_precision(HIGHEST):
        qk = jnp.einsum("rmd,sd->rms", q, k)
        power = qk if "degree_one" in faults else qk * qk
        if "no_sqrt2" in faults:
            # phi's cross terms at weight 1: sum_{a<=b}, not (q . k)^2
            power = 0.5 * (power + jnp.einsum("rmd,sd->rms", q * q, k * k))
        seg = A[t][:, None] - A[None, :]
        if "own_term_decayed" in faults:
            # the gate applied to a token's own term too
            seg = seg + a[None, :]
        w = jnp.where(seen, power * jnp.exp(jnp.where(seen, seg, 0.0)), 0.0)
        num = jnp.einsum("rms,sd->rmd", w, v)
        if "no_normaliser" in faults:
            return num
        if "sum_not_decayed" in faults:
            # z keeps every key at full weight while S forgets
            w = jnp.where(seen, power, 0.0)
        return num / jnp.sum(w, axis=-1, keepdims=True)


def mixer_out(hn, w, cfg, faults=frozenset(), kept: dict = None):
    """The normed input hn [s, h] -> what the retention mixer adds [s, h];
    with a dict for ``kept`` its k, v [s, kv, d] and a [s, kv]."""
    s = hn.shape[0]
    n_heads, n_kv = (int(cfg["num_attention_heads"]),
                     int(cfg["num_key_value_heads"]))
    r = n_heads // n_kv
    q, k, v, a = mixer_inputs(
        hn, {n: w[n] for n in ("wq", "wk", "wv", "wg", "q_norm", "k_norm")},
        n_heads=n_heads, n_kv=n_kv, theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]),
        faults=faults & (PRECISION | {"no_qk_norm", "no_rope", "no_gate"}))
    if kept is not None:
        kept.update(k=k, v=v, a=a)
    A = jnp.cumsum(a, axis=0)
    inner = faults & {"degree_one", "no_normaliser", "no_sqrt2",
                      "own_term_decayed", "sum_not_decayed",
                      "state_dropped_at_chunks"}
    heads = []
    for g in range(n_kv):
        # a query head reading its neighbour's key-value head
        src = (g + 1) % n_kv if "kv_neighbour" in faults else g
        qg = jnp.moveaxis(q[:, g * r:(g + 1) * r], 0, 1)    # [r, s, d]
        out = jnp.concatenate([
            retain_block(qg[:, f:f + QUERY_BLOCK], k[:, src], v[:, src],
                         A[:, src], a[:, src], f, faults=inner,
                         chunk=int(cfg.get("fault_chunk", CHUNK)))
            for f in range(0, s, QUERY_BLOCK)], axis=1)
        heads.append(jnp.moveaxis(out, 0, 1))               # [s, r, d]
    ctx = _rounded(jnp.concatenate(heads, axis=1).reshape(s, -1), faults)
    with jax.default_matmul_precision(HIGHEST):
        return ctx @ w["wo"]


@jax.jit
def _swiglu(m, w1, w3, w2):
    with jax.default_matmul_precision(HIGHEST):
        return (jax.nn.silu(m @ w1) * (m @ w3)) @ w2


def mlp_out(m, weights, i: int, width: int, faults=frozenset()):
    """SwiGLU in column blocks of ``MLP_BLOCK`` (a sum over blocks)."""
    y = jnp.zeros_like(m)
    m = _rounded(m, faults)
    for lo in range(0, width, MLP_BLOCK):
        y = y + _swiglu(m, *weights.mlp_block(i, lo, min(lo + MLP_BLOCK,
                                                         width)))
    return y


@functools.partial(jax.jit, static_argnames=("eps",))
def normed(x, w, *, eps):
    return rms_norm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_block(x, norm, output_rows, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, norm, eps) @ output_rows.T


def phi_rotations(x):
    """x [..., d] -> [..., d/2 + 1, d]: the program's stated layout of
    ``phi`` (``assumed.phi_layout``), written here from the statement:
    rotation o holds ``c_o x[a] x[(a + o) mod d]``, ``c`` 1 at o = 0 and
    o = d/2 and sqrt 2 between."""
    d = x.shape[-1]
    a = np.arange(d)
    o = np.arange(d // 2 + 1)
    c = np.where((o == 0) | (o == d // 2), 1.0, math.sqrt(2.0))
    return (jnp.asarray(c, jnp.float32)[:, None] * x[..., None, :]
            * x[..., (a[None, :] + o[:, None]) % d])


@jax.jit
def state_of(k, v, a):
    """k, v [s, kv, d] and a [s, kv] of one layer -> (S [kv, O, d (value),
    d], z [kv, O, d]) after the sequence's last token, in the program's
    layout."""
    A = jnp.cumsum(a, axis=0)
    to_end = jnp.exp(A[-1:] - A)                            # [s, kv]
    with jax.default_matmul_precision(HIGHEST):
        pk = phi_rotations(k) * to_end[..., None, None]     # [s, kv, O, d]
        return jnp.einsum("sgoa,sgd->goda", pk, v), pk.sum(axis=0)


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None, rows=None, faults=frozenset(),
                   kept: dict = None) -> jax.Array:
    """tokens [s] -> logits [s, vocab] (float32), or [len(rows), vocab]
    at the positions ``rows``.  ``kept`` {layer: {}}: that layer's mixer
    leaves its k, v and a there (``state_of`` takes them).  The model is
    dense: ``router_margins`` gets nothing and ``turned`` is refused."""
    if turned:
        raise NotImplementedError("a dense model turns no tie")
    tokens = np.asarray(tokens, np.int32)
    faults = frozenset(faults)
    assert faults <= set(FAULTS), faults
    eps = float(cfg["rms_norm_eps"])
    x = weights.embedding_rows(tokens)
    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        y = mixer_out(normed(x, w["input_norm"], eps=eps), w, cfg, faults,
                      None if kept is None else kept.get(i))
        x = x + y
        x = x + mlp_out(normed(x, w["post_norm"], eps=eps), weights, i,
                        int(cfg["intermediate_size"]), faults & PRECISION)
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
