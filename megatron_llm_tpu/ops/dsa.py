"""Learned sparse attention (DeepSeek Sparse Attention): the indexer's
scores, the exact choice of a query's keys, and attention under that
choice, in plain ``jax.numpy``.

An indexer of ``Hi`` small heads and ONE key head scores every earlier
position for a query at position t::

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        s <= t

and the query attends only ``S_t``, the positions of the ``topk``
largest ``I[t, s]`` over ``s <= t`` (equal scores: the earlier position
first); every ``s <= t`` while there are no more than ``topk``.  The
choice is exact: no approximate top-k anywhere.

This file is what runs where the Pallas kernels cannot (the CPU, a
program over several devices), what the cache-less forward runs
everywhere, and what the kernels of ``ops/pallas/dsa_attention.py`` are
tested against.  ``ops/paged_kv.py`` decides which of the two a paged
program takes.

The choice (``choose``) is made with no sort.  The ``topk``-th largest
score of a row is found exactly by building its bit pattern from the top
bit down (32 counts over the row: a float's bits, with the sign folded,
order as the floats do), which gives the mask of what lies above it;
among scores EQUAL to it the earliest positions fill what is left, found
the same way over positions.  The Pallas kernel runs the same function
over its own layout, and over a row's live blocks only.
``jax.lax.top_k`` has the same tie rule (the lower index first) and is
what the plain reference uses (``benchmarks/reference/keye.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
# plain numbers, not arrays: a Pallas kernel may not capture an array
_SIGN = np.int32(-2 ** 31)
_MAGNITUDE = np.int32(0x7fffffff)


def index_scores(iq: jax.Array, ik: jax.Array, iw: jax.Array) -> jax.Array:
    """``iq`` [b, n, Hi, di], ``ik`` [b, T, di] (compute dtype), ``iw``
    [b, n, Hi] fp32 -> ``I`` [b, n, T] fp32 (no mask applied)."""
    s = jnp.einsum("bnhd,btd->bnht", iq, ik,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bnht,bnh->bnt", jax.nn.relu(s),
                      iw.astype(jnp.float32))


def ordered_bits(x: jax.Array) -> jax.Array:
    """fp32 -> int32 whose (signed) order is the floats' order: a
    negative's magnitude bits flipped, a positive's bits as they are."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(b < 0, b ^ _MAGNITUDE, b)


def choose(key, valid, pos, topk: int, pos_bits: int, count):
    """bool like ``key``: for each row its ``topk`` largest valid entries
    of ``key`` (``ordered_bits``), equal ones taken from the smallest
    ``pos``; every valid entry of a row that has no more than ``topk``.
    ``count(cond)`` counts a row's true entries (keeping the row's
    shape to broadcast against): the one thing that depends on how rows
    are laid out, so the Pallas kernel hands in its own.  ``key``,
    ``valid`` and ``pos`` are only compared and joined with ``& |`` here
    and looked into by ``count`` alone, so the kernel hands in rows it
    holds a few blocks at a time, and gets such a row back.  ``pos`` < 2
    ** ``pos_bits``."""
    k = jnp.minimum(count(valid), topk)
    zero = jnp.zeros(k.shape, jnp.int32)

    def value_bit(i, prefix):
        # prefix: the answer's bits so far, in UNSIGNED order (the signed
        # order with the top bit flipped); try the next bit set
        cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
        enough = count(valid & (key >= (cand ^ _SIGN))) >= jnp.maximum(k, 1)
        return jnp.where(enough, cand, prefix)

    # the k-th largest: the largest value with k valid entries at or above
    kth = jax.lax.fori_loop(0, 32, value_bit, zero) ^ _SIGN
    above = valid & (key > kth)
    equal = valid & (key == kth)
    need = k - count(above)               # of the equal ones, the earliest

    def position_bit(i, bound):
        # the largest bound with no more than ``need`` equal entries below
        cand = bound | jnp.left_shift(jnp.int32(1), pos_bits - 1 - i)
        fits = count(equal & (pos < cand)) <= need
        return jnp.where(fits, cand, bound)

    bound = jax.lax.fori_loop(0, pos_bits, position_bit, zero)
    return above | (equal & (pos < bound))


def select_mask(scores: jax.Array, valid: jax.Array, topk: int
                ) -> jax.Array:
    """bool [..., T]: for each row the ``topk`` largest valid scores,
    equal scores taken from the earliest position; every valid entry of a
    row that has no more than ``topk``."""
    T = scores.shape[-1]
    return choose(
        ordered_bits(scores), valid, jnp.arange(T, dtype=jnp.int32), topk,
        max(1, math.ceil(math.log2(T + 1))),
        lambda c: jnp.sum(c.astype(jnp.int32), axis=-1, keepdims=True))


def masked_attention(q, k, v, mask, scale):
    """q [b, n, nh, d], k / v [b, T, g, d], mask [b, n, T] -> [b, n, nh,
    d]: softmax over the masked keys in fp32 (a row with none: zeros)."""
    b, n, nh, d = q.shape
    g = k.shape[2]
    qg = q.reshape(b, n, g, nh // g, d)
    s = jnp.einsum("bngpd,btgd->bgpnt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    m = mask[:, None, None]
    s = jnp.where(m, s, NEG_INF)
    p = jnp.where(m, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bgpnt,btgd->bngpd", p, v.astype(jnp.float32))
    return out.reshape(b, n, nh, d).astype(q.dtype)


def selected_attention(q, k, v, iq, ik, iw, q_pos, topk, scale):
    """Attention of ``q`` [b, n, nh, d] at positions ``q_pos`` [b, n]
    over keys ``k`` / ``v`` [b, T, g, d] lying at positions 0..T-1, under
    the indexer's choice: key s is open to a query at position t when
    ``s <= t``."""
    T = k.shape[1]
    valid = jnp.arange(T)[None, None, :] <= q_pos[:, :, None]
    chosen = select_mask(index_scores(iq, ik, iw), valid, topk)
    return masked_attention(q, k, v, chosen, scale)


def causal_selected_attention(q, k, v, iq, ik, iw, topk, *,
                              block_q: int = 256):
    """The cache-less forward: one sequence a row, query i at position i
    over keys 0..i, in blocks of ``block_q`` queries so that the scores
    of a block, not of the sequence, are held at once."""
    b, n, nh, d = q.shape
    scale = 1.0 / math.sqrt(d)
    bq = min(block_q, n)
    pad = -n % bq

    def padded(x):
        return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))

    def blocks(x):                       # [b, n, ...] -> [n/bq, b, bq, ...]
        x = padded(x)
        return jnp.moveaxis(
            x.reshape((b, (n + pad) // bq, bq) + x.shape[2:]), 1, 0)

    pos = jnp.broadcast_to(jnp.arange(n)[None], (b, n))

    def one(args):
        qb, iqb, iwb, pb = args
        return selected_attention(qb, k, v, iqb, ik, iwb, pb, topk, scale)

    out = jax.lax.map(one, (blocks(q), blocks(iq), blocks(iw), blocks(pos)))
    return jnp.moveaxis(out, 0, 1).reshape(b, n + pad, nh, d)[:, :n]
