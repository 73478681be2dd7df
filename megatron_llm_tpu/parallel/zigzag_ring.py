"""Zigzag ring attention — load-balanced causal context parallelism
(cp algorithm #3, alongside ``ring_attention`` and ``ulysses``).

The plain ring (``parallel/ring_attention.py``) computes masked scores
for every (q-chunk, kv-chunk) pair: under causal masking, the kv chunks
a rank receives in most ring steps are entirely in its future, so
~half the computed score blocks are fully masked — and the USEFUL work
is imbalanced (rank r's q attends r+1 of the P kv chunks).  Since the
ring is lockstep (a ``ppermute`` barrier every step), wall-clock follows
the heaviest rank.

The zigzag layout fixes both (the scheme used for Llama-3 long-context
training; public zigzag/striped ring-attention implementations use the
same assignment): split the global sequence into 2P half-chunks and give
rank r the PAIR (r, 2P-1-r) — one early chunk, one late chunk.  Every
rank then owns the same amount of "causal past", so per ring step each
rank has the same number of live (q-half, kv-half) sub-blocks, and the
fully-masked sub-blocks are skipped with ``lax.cond`` — compute per step
is balanced AND roughly halved instead of masked-then-discarded.

Data stays contiguously sharded outside this module (same shard_map
specs as ring); the zigzag redistribution is two ``ppermute`` bijections
on entry and their inverses on exit (~2 extra ICI hops, amortized over
the P-step ring).

Exactness: the accumulator is the standard streaming-softmax (m, l, acc)
triple per q half; results equal plain ring / full attention to fp32
associativity (tests/test_zigzag.py).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu import topology
from megatron_llm_tpu.parallel.ring_attention import (
    DEFAULT_Q_CHUNK,
    NEG_INF,
    _chunk_scores,
)


def _zig_owner(c, P_sz):
    """Zigzag owner rank of global half-chunk c (0 <= c < 2P)."""
    return c if c < P_sz else 2 * P_sz - 1 - c


def _to_zigzag(x, axis_name, cp):
    """Contiguous rank r holds half-chunks (2r, 2r+1) of its seq axis
    (axis 1); redistribute so rank g holds (g, 2P-1-g), returned as
    (low, high) arrays of half length."""
    s = x.shape[1]
    h0, h1 = x[:, : s // 2], x[:, s // 2:]
    perm_a = [(i, _zig_owner(2 * i, cp)) for i in range(cp)]
    perm_b = [(i, _zig_owner(2 * i + 1, cp)) for i in range(cp)]
    got_a = lax.ppermute(h0, axis_name, perm_a)   # carries chunk 2i
    got_b = lax.ppermute(h1, axis_name, perm_b)   # carries chunk 2i+1
    g = lax.axis_index(axis_name)
    # permA delivers chunk g when g is even (2i = g), else chunk 2P-1-g;
    # permB is complementary — order into (low=chunk g, high=chunk 2P-1-g)
    even = (g % 2) == 0
    low = jnp.where(even, got_a, got_b)
    high = jnp.where(even, got_b, got_a)
    return low, high


def _from_zigzag(low, high, axis_name, cp):
    """Inverse of :func:`_to_zigzag`: rank g holds chunks (g, 2P-1-g);
    return the contiguous local [s] = chunks (2r, 2r+1)."""
    g = lax.axis_index(axis_name)
    even = (g % 2) == 0
    # invert the forward bijections: Ainv returns the permA-delivered
    # chunk (the low one on even ranks) to its contiguous owner as h0
    via_a = jnp.where(even, low, high)
    via_b = jnp.where(even, high, low)
    perm_a_inv = [(_zig_owner(2 * i, cp), i) for i in range(cp)]
    perm_b_inv = [(_zig_owner(2 * i + 1, cp), i) for i in range(cp)]
    h0 = lax.ppermute(via_a, axis_name, perm_a_inv)
    h1 = lax.ppermute(via_b, axis_name, perm_b_inv)
    return jnp.concatenate([h0, h1], axis=1)


def zigzag_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    q_chunk_size: int = DEFAULT_Q_CHUNK,
) -> jax.Array:
    """Inside shard_map: q/k/v [b, s_local, heads, d], sequence
    contiguously sharded over ``axis_name``; returns the same layout.
    See module docstring for the algorithm."""
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    cp = lax.psum(1, axis_name)
    g = lax.axis_index(axis_name)
    b, s, nh, d = q.shape
    ng = k.shape[2]
    qpg = nh // ng
    cs = s // 2                       # half-chunk length
    assert s % 2 == 0, "zigzag needs an even local sequence length"

    q_low, q_high = _to_zigzag(q, axis_name, cp)
    k_low, k_high = _to_zigzag(k, axis_name, cp)
    v_low, v_high = _to_zigzag(v, axis_name, cp)

    # this rank's q half-chunk ids (traced scalars)
    q_ids = (g, 2 * cp - 1 - g)
    q_parts = (q_low, q_high)

    # q rows are processed qc at a time inside each sub-block (same
    # bound as ring_self_attention: peak score memory [b, heads, qc, cs]
    # instead of [b, heads, cs, cs], which at long local sequences is
    # the [s, s]-scale tensor this stack cannot compile)
    qc = min(q_chunk_size, cs)
    while cs % qc != 0:
        qc -= 1
    n_qc = cs // qc

    def sub_block(q_i, q_id, k_c, v_c, k_id, m_a, l_a, a_a):
        """Streaming-softmax update of one (q-half, kv-half) pair,
        skipped entirely (lax.cond) when causally fully masked."""
        k_pos = k_id * cs + jnp.arange(cs)

        def live(args):
            def q_block(ci, carry_q):
                m_x, l_x, a_x = carry_q
                q_c = lax.dynamic_slice_in_dim(q_i, ci * qc, qc, axis=1)
                q_pos = q_id * cs + ci * qc + jnp.arange(qc)
                scores = _chunk_scores(q_c, k_c, softmax_scale)
                mask = jnp.ones((qc, cs), bool)
                if causal:
                    mask &= k_pos[None, :] <= q_pos[:, None]
                if sliding_window is not None:
                    mask &= k_pos[None, :] > q_pos[:, None] - sliding_window
                scores = jnp.where(mask[None, None, None], scores, NEG_INF)
                m_prev = lax.dynamic_slice_in_dim(m_x, ci * qc, qc, axis=3)
                l_prev = lax.dynamic_slice_in_dim(l_x, ci * qc, qc, axis=3)
                a_prev = lax.dynamic_slice_in_dim(a_x, ci * qc, qc, axis=3)
                m_c = jnp.max(scores, axis=-1)
                m_new = jnp.maximum(m_prev, m_c)
                p = jnp.exp(scores - m_new[..., None])
                p = jnp.where(mask[None, None, None], p, 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_new = l_prev * alpha + jnp.sum(p, axis=-1)
                o_c = jnp.einsum("bgpst,btgd->bgpsd", p,
                                 v_c.astype(jnp.float32))
                a_new = a_prev * alpha[..., None] + o_c
                return (
                    lax.dynamic_update_slice_in_dim(m_x, m_new, ci * qc, 3),
                    lax.dynamic_update_slice_in_dim(l_x, l_new, ci * qc, 3),
                    lax.dynamic_update_slice_in_dim(a_x, a_new, ci * qc, 3),
                )

            return lax.fori_loop(0, n_qc, q_block, args)

        skip = jnp.bool_(False)
        if causal:
            # kv half entirely in this q half's future
            skip = skip | (k_id > q_id)
        if sliding_window is not None:
            # kv half entirely before the window of every q row
            skip = skip | ((k_id + 1) * cs - 1 <= q_id * cs - sliding_window)
        return lax.cond(skip, lambda args: args, live, (m_a, l_a, a_a))

    def step(carry, _):
        k_l, k_h, v_l, v_h, src, accs = carry
        accs_new = []
        for qi in range(2):
            m_a, l_a, a_a = accs[qi]
            # incoming kv pair holds half-chunks (src, 2P-1-src)
            m_a, l_a, a_a = sub_block(q_parts[qi], q_ids[qi],
                                      k_l, v_l, src, m_a, l_a, a_a)
            m_a, l_a, a_a = sub_block(q_parts[qi], q_ids[qi],
                                      k_h, v_h, 2 * cp - 1 - src,
                                      m_a, l_a, a_a)
            accs_new.append((m_a, l_a, a_a))

        perm = [(i, (i + 1) % cp) for i in range(cp)]
        k_l2 = lax.ppermute(k_l, axis_name, perm)
        k_h2 = lax.ppermute(k_h, axis_name, perm)
        v_l2 = lax.ppermute(v_l, axis_name, perm)
        v_h2 = lax.ppermute(v_h, axis_name, perm)
        return (k_l2, k_h2, v_l2, v_h2, (src - 1) % cp,
                tuple(accs_new)), None

    def init_acc():
        return (jnp.full((b, ng, qpg, cs), NEG_INF, jnp.float32),
                jnp.zeros((b, ng, qpg, cs), jnp.float32),
                jnp.zeros((b, ng, qpg, cs, d), jnp.float32))

    carry0 = (k_low, k_high, v_low, v_high, g, (init_acc(), init_acc()))
    (_, _, _, _, _, accs), _ = lax.scan(
        jax.checkpoint(step), carry0, None, length=cp)

    outs = []
    for m, l, acc in accs:
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = (acc / l_safe[..., None]).astype(q.dtype)  # [b,g,p,cs,d]
        outs.append(jnp.moveaxis(o, 3, 1).reshape(b, cs, nh, d))
    return _from_zigzag(outs[0], outs[1], axis_name, cp)


def zigzag_context_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    q_chunk_size: int = DEFAULT_Q_CHUNK,
):
    """shard_map wrapper mirroring ``context_parallel_attention``:
    global arrays with the sequence axis contiguously sharded over cp;
    nests under the pipeline engines' manual regions via
    ``topology.nesting_mesh``."""
    mesh, _ = topology.nesting_mesh(topology.CP_AXIS)
    if mesh is None:
        raise RuntimeError(
            "zigzag_context_attention called with no usable 'cp' axis in "
            "scope (callers gate on get_context_parallel_world_size() > 1)")
    fn = partial(
        zigzag_self_attention,
        axis_name=topology.CP_AXIS,
        causal=causal,
        sliding_window=sliding_window,
        softmax_scale=softmax_scale,
        q_chunk_size=q_chunk_size,
    )
    spec = P(None, topology.CP_AXIS, None, None)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names={topology.CP_AXIS},
        check_vma=False,
    )(q, k, v)
