"""Ring attention: context-parallel exact attention for long sequences.

The reference has NO long-context attention parallelism (SURVEY §5.7 —
no ring attention, no Ulysses; its sequence parallelism is activation
*memory* sharding only).  This module is the TPU-native long-context
design: the sequence axis of activations is sharded over the ``cp`` mesh
axis, every device holds a contiguous Q chunk, and K/V chunks rotate
around the cp ring with ``lax.ppermute`` (one ICI hop per step) while each
device accumulates its Q-chunk's attention with the online-softmax
combine.  cp_size - 1 hops overlap with the chunk attention compute —
the classic Ring Attention schedule (Liu et al.) on XLA collectives.

Causality needs no per-step case analysis: the mask is derived from
*global* positions (rank * chunk + local index), so chunks from earlier in
the ring contribute fully, the diagonal chunk causally, later ones not at
all.  Autodiff through the scan + ppermute derives the reverse ring for
the backward pass.

Used inside ``shard_map`` manual over {'cp'} (dp/tp stay GSPMD-auto);
attention dispatch in ``models/transformer.py`` routes here when the mesh
has cp > 1.
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

NEG_INF = -1e30


def _chunk_scores(q, k, scale):
    # q [b, sq, nh, d]; k [b, sk, ng, d] -> scores [b, ng, qpg, sq, sk] f32
    b, sq, nh, d = q.shape
    ng = k.shape[2]
    qpg = nh // ng
    qg = q.reshape(b, sq, ng, qpg, d)
    return jnp.einsum("bsgpd,btgd->bgpst", qg, k).astype(jnp.float32) * scale


DEFAULT_Q_CHUNK = 1024


def ring_self_attention(
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
    """Exact attention over a cp-sharded sequence, inside shard_map.

    q/k/v: local chunks [b, s_local, heads, d]; sequence is contiguously
    sharded over ``axis_name`` (chunk r holds global positions
    [r*s_local, (r+1)*s_local)).

    Each ring step processes Q in ``q_chunk_size`` rows at a time (an
    inner scan), so peak score memory is [b, heads, qc, s_local] instead
    of [b, heads, s_local, s_local] — at 8k-per-device sequences that is
    the difference between ~0.5 GB and ~4 GB of fp32 scores per step.
    Q-rows are independent in attention, so the chunking is exact.
    """
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    cp = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, s, nh, d = q.shape
    ng = k.shape[2]
    qpg = nh // ng
    # largest chunk <= q_chunk_size that divides s (a non-divisor would
    # let dynamic_slice clamp the final block and double-count tail rows)
    qc = min(q_chunk_size, s)
    while s % qc != 0:
        qc -= 1
    n_qc = s // qc

    def step(carry, _):
        kv, src, m_acc, l_acc, acc = carry
        k_c, v_c = kv
        k_pos = src * s + jnp.arange(s)

        def q_block(ci, carry_q):
            m_a, l_a, a_a = carry_q
            q_i = lax.dynamic_slice_in_dim(q, ci * qc, qc, axis=1)
            q_pos = my * s + ci * qc + jnp.arange(qc)
            scores = _chunk_scores(q_i, k_c, softmax_scale)  # [b,g,p,qc,s]
            mask = jnp.ones((qc, s), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if sliding_window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - sliding_window
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)

            m_prev = lax.dynamic_slice_in_dim(m_a, ci * qc, qc, axis=3)
            l_prev = lax.dynamic_slice_in_dim(l_a, ci * qc, qc, axis=3)
            a_prev = lax.dynamic_slice_in_dim(a_a, ci * qc, qc, axis=3)
            m_c = jnp.max(scores, axis=-1)               # [b, g, p, qc]
            m_new = jnp.maximum(m_prev, m_c)
            p = jnp.exp(scores - m_new[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            o_c = jnp.einsum("bgpst,btgd->bgpsd", p,
                             v_c.astype(jnp.float32))
            a_new = a_prev * alpha[..., None] + o_c
            return (
                lax.dynamic_update_slice_in_dim(m_a, m_new, ci * qc, 3),
                lax.dynamic_update_slice_in_dim(l_a, l_new, ci * qc, 3),
                lax.dynamic_update_slice_in_dim(a_a, a_new, ci * qc, 3),
            )

        m_acc, l_acc, acc = lax.fori_loop(
            0, n_qc, q_block, (m_acc, l_acc, acc))

        # rotate K/V to the next ring position.  The final rotation's
        # result is discarded (the carry ends the scan) — one redundant
        # ICI hop per call, accepted to keep the scan body uniform; a
        # cond-guarded collective would cost more in program complexity
        # than the 1/cp bandwidth it saves.
        perm = [(i, (i + 1) % cp) for i in range(cp)]
        kv_next = (lax.ppermute(k_c, axis_name, perm),
                   lax.ppermute(v_c, axis_name, perm))
        src_next = (src - 1) % cp
        return (kv_next, src_next, m_acc, l_acc, acc), None

    m0 = jnp.full((b, ng, qpg, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, ng, qpg, s), jnp.float32)
    acc0 = jnp.zeros((b, ng, qpg, s, d), jnp.float32)
    (_, _, m, l, acc), _ = lax.scan(
        jax.checkpoint(step), ((k, v), my, m0, l0, acc0), None, length=cp
    )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).astype(q.dtype)    # [b, ng, qpg, s, d]
    return jnp.moveaxis(out, 3, 1).reshape(b, s, nh, d)


def context_parallel_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    q_chunk_size: int = DEFAULT_Q_CHUNK,
):
    """shard_map wrapper: q/k/v are global arrays with the sequence axis
    sharded over cp ('batch','seq_cp',heads,d); returns same layout.

    Nests under the pipeline engine's pp-manual shard_map: inside a manual
    region jax requires the *abstract* context mesh (whose pp axis is
    already Manual; ``topology.nesting_mesh``), and this region names cp
    alone."""
    mesh, _ = topology.nesting_mesh(topology.CP_AXIS)
    if mesh is None:
        raise RuntimeError(
            "context_parallel_attention called with no usable 'cp' axis in "
            "scope (callers gate on get_context_parallel_world_size() > 1; "
            "an enclosing custom mesh without a cp axis cannot host ring "
            "attention)")
    fn = partial(
        ring_self_attention,
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
