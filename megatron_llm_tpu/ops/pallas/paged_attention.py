"""Ragged paged-attention kernels for the serving engine (Pallas Mosaic
TPU) — decode (one query token per slot) and chunked prefill (a [C]-token
query block per slot) share one kernel body.

The XLA paged branch in ``models/transformer.py`` gathers every slot's
FULL block table into a dense ``[b, M*bs, g, d]`` view (dequantizing
every int8 page) before masked attention — each call moves the
worst-case context for every slot.  These kernels walk each slot's block
table directly in the grid instead, reading only the pages the slot
actually owns (arXiv:2604.15464 is the blueprint; paged-KV HBM traffic
is the serving throughput ceiling, arXiv:2605.25645).

Shape contract (the serving engine's paged programs):

* ``q`` — decode ``[S, nh, d]``: ONE query token per slot; prefill
  ``[S, C, nh, d]``: a C-token chunk per slot (the engine's ``[1, C]``
  chunked-prefill call).
* ``k_pages``/``v_pages`` — ``[P, bs, g, d]`` shared page pool, already
  containing this call's scatter-on-write (the query tokens' K/V sit at
  positions ``context_lens[s] .. context_lens[s]+C-1``).  int8 pools
  ship per-(page, position, group) fp32 absmax scales ``[P, bs, g]``
  and are dequantized in-kernel, so int8 is what crosses HBM.
* ``block_tables`` — ``[S, M]`` int32, entries beyond a slot's
  allocation = 0 (the reserved garbage block).
* ``context_lens`` — ``[S]`` int32: tokens already cached BEFORE this
  call's queries.  Decode attends keys ``0..context_lens[s]``
  inclusive; prefill row ``j`` attends ``0..context_lens[s]+j`` (causal
  within the chunk on top of the full paged history).  A sliding window
  additionally drops ``key_pos <= query_pos - window``.

Kernel structure: grid ``(slot, q-block, page)`` with the page dimension
innermost — sequential on TPU, so fp32 scratch (m, l, acc) carries the
online-softmax state across a (slot, q-block)'s pages.  The page index
map clamps out-of-range grid steps to the nearest live page: Mosaic
skips the DMA when consecutive grid steps map a block to the same index,
so a slot with 3 live pages out of M=128 moves exactly 3 pages of KV per
q-block.  All query heads ride in one block per grid step (GQA groups
are a static in-kernel loop), so each page is fetched once, not once per
head.  Decode is the ``C == block_q == 1`` instance of the same body —
one scaffold, two entry points.

Dispatch mirrors ``flash_attention.py``: TPU backend -> kernel;
otherwise -> jnp reference math (the same dense-gather computation as
the transformer's XLA branch).  Interpret-mode tests run the kernels on
CPU via the module-level ``_INTERPRET`` flag.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = False
NEG_INF = -1e30
# default prefill q-block rows (clipped to the chunk; kept MXU-sized so
# the fp32 scratch [block_q*nh, d] stays well inside VMEM)
_PREFILL_BLOCK_Q = 128


def _use_pallas() -> bool:
    from megatron_llm_tpu.ops.pallas import pallas_backend_available

    return _INTERPRET or pallas_backend_available()


def decode_kernel_available() -> bool:
    """True when ``paged_attention_decode`` would run the Pallas kernel
    (TPU backend, or interpret mode in tests) — the transformer's
    ``--serve_paged_kernel auto`` predicate and the engine's
    ``paged_kernel: pallas|xla`` attribution both key off this."""
    return _use_pallas()


def prefill_kernel_available() -> bool:
    """Same gate for ``paged_attention_prefill`` (the kernels share a
    backend, so today this equals :func:`decode_kernel_available`; kept
    separate so ``--serve_prefill_kernel auto`` and the engine's
    ``prefill_kernel`` attribution have their own seam)."""
    return _use_pallas()


# ---------------------------------------------------------------------------
# reference math (non-TPU fallback; identical to the XLA paged branch)
# ---------------------------------------------------------------------------

def _reference_paged_prefill(q, k_pages, v_pages, block_tables,
                             context_lens, k_scales, v_scales,
                             scale, window):
    """Dense-gather chunked prefill: q [S, C, nh, d], row ``j`` of slot
    ``s`` attends key positions ``0..context_lens[s]+j`` (minus the
    sliding window) — the same math as the transformer's XLA branch."""
    S, C, nh, d = q.shape
    bs, g = k_pages.shape[1], k_pages.shape[2]
    M = block_tables.shape[1]
    qpg = nh // g
    k = k_pages[block_tables].reshape(S, M * bs, g, d).astype(jnp.float32)
    v = v_pages[block_tables].reshape(S, M * bs, g, d).astype(jnp.float32)
    if k_scales is not None:
        k = k * k_scales[block_tables].reshape(S, M * bs, g, 1)
        v = v * v_scales[block_tables].reshape(S, M * bs, g, 1)
    qg = q.reshape(S, C, g, qpg, d).astype(jnp.float32)
    scores = jnp.einsum("bsgpd,btgd->bgpst", qg, k) * scale
    key_pos = jnp.arange(M * bs)
    pos = context_lens[:, None] + jnp.arange(C)[None, :]        # [S, C]
    valid = key_pos[None, None, :] <= pos[:, :, None]           # [S, C, T]
    if window is not None:
        valid &= key_pos[None, None, :] > (pos[:, :, None] - window)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgpst,btgd->bsgpd", probs, v)
    return out.reshape(S, C, nh, d).astype(q.dtype)


def _reference_paged_attention(q, k_pages, v_pages, block_tables,
                               context_lens, k_scales, v_scales,
                               scale, window):
    """Decode reference — the C == 1 instance of the prefill reference."""
    return _reference_paged_prefill(
        q[:, None], k_pages, v_pages, block_tables, context_lens,
        k_scales, v_scales, scale, window)[:, 0]


# ---------------------------------------------------------------------------
# shared ragged kernel body (decode == block_q 1)
# ---------------------------------------------------------------------------

def _ragged_body(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
                 m_scr, l_scr, acc_scr,
                 *, ks_ref, vs_ref, scale, block_size, block_q, window, qpg):
    s = pl.program_id(0)
    qi = pl.program_id(1)
    pi = pl.program_id(2)
    npi = pl.num_programs(2)
    bs = block_size
    bq = block_q
    g = k_ref.shape[2]
    d = k_ref.shape[3]
    # scratch rows per GQA group: the q-block's [bq, qpg, d] query slice
    # flattened to [R, d] so scores stay 2-D for the MXU; flat row r is
    # (chunk row r // qpg, in-group head r % qpg)
    R = bq * qpg

    @pl.when(pi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ctx = cl_ref[s]                       # keys cached before this call
    q0 = qi * bq                          # first chunk row of this q-block
    last = (ctx + q0 + bq - 1) // bs      # newest page any row attends
    if window is None:
        first = 0
    else:
        first = jnp.maximum(ctx + q0 - window + 1, 0) // bs

    @pl.when((pi >= first) & (pi <= last))
    def _compute():
        k = k_ref[0].astype(jnp.float32)              # [bs, g, d]
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0][:, :, None]             # [bs, g] scales
            v = v * vs_ref[0][:, :, None]
        qh = q_ref[0].astype(jnp.float32)             # [bq, nh, d]
        key_pos = pi * bs + jax.lax.broadcasted_iota(
            jnp.int32, (R, bs), 1)
        # per-row causal bound: flat row r belongs to chunk row r // qpg
        pos = ctx + q0 + jax.lax.broadcasted_iota(
            jnp.int32, (R, bs), 0) // qpg
        valid = key_pos <= pos
        if window is not None:
            valid &= key_pos > pos - window
        # one page DMA serves every query head: GQA groups are a static
        # unrolled loop over the head block's row slices
        for grp in range(g):
            rows = slice(grp * R, (grp + 1) * R)
            q2 = qh[:, grp * qpg:(grp + 1) * qpg, :].reshape(R, d)
            sq = jax.lax.dot_general(
                q2, k[:, grp, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                 # [R, bs]
            sq = jnp.where(valid, sq, NEG_INF)
            m_prev = m_scr[rows]                      # [R, 1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(sq, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(sq - m_new), 0.0)
            l_scr[rows] = l_scr[rows] * alpha + jnp.sum(p, axis=-1,
                                                        keepdims=True)
            acc_scr[rows] = acc_scr[rows] * alpha + jax.lax.dot(
                p, v[:, grp, :], preferred_element_type=jnp.float32)
            m_scr[rows] = m_new

    @pl.when(pi == npi - 1)
    def _finish():
        outs = []
        for grp in range(g):
            rows = slice(grp * R, (grp + 1) * R)
            l = l_scr[rows]                           # [R, 1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            outs.append((acc_scr[rows] / l_safe).reshape(bq, qpg, d))
        out = outs[0] if g == 1 else jnp.concatenate(outs, axis=1)
        o_ref[0] = out.astype(o_ref.dtype)            # [bq, nh, d]


def _ragged_kernel_plain(bt, cl, q, k, v, o, m, l, acc, **kw):
    _ragged_body(bt, cl, q, k, v, o, m, l, acc,
                 ks_ref=None, vs_ref=None, **kw)


def _ragged_kernel_quant(bt, cl, q, k, ks, v, vs, o, m, l, acc, **kw):
    _ragged_body(bt, cl, q, k, v, o, m, l, acc,
                 ks_ref=ks, vs_ref=vs, **kw)


def _ragged_call(q, k_pages, v_pages, block_tables, context_lens,
                 k_scales, v_scales, *, scale, window, block_q, name):
    """Shared pallas_call scaffold: q [S, C, nh, d] with block_q | C.
    Decode is the C == block_q == 1 instance.  ``name`` is the kernel's
    name in a profile (``_quant`` appended for the int8 pools)."""
    S, C, nh, d = q.shape
    bs, g = k_pages.shape[1], k_pages.shape[2]
    M = block_tables.shape[1]
    qpg = nh // g
    bq = block_q
    assert C % bq == 0, (C, bq)
    nq = C // bq
    quantized = k_scales is not None

    def page_map(s, qi, pi, bt_ref, cl_ref):
        # clamp out-of-range grid steps to the nearest page this
        # (slot, q-block) attends: Mosaic skips the block copy when
        # consecutive steps map to the same index, so only the live
        # pages up to ceil((ctx + (qi+1)*bq)/bs) (minus any fully
        # outside the sliding window) are fetched
        hi = jnp.minimum((cl_ref[s] + (qi + 1) * bq - 1) // bs, M - 1)
        lo = (jnp.maximum(cl_ref[s] + qi * bq - window + 1, 0) // bs
              if window is not None else 0)
        return (bt_ref[s, jnp.clip(pi, lo, hi)], 0, 0, 0)

    def scale_map(s, qi, pi, bt_ref, cl_ref):
        return page_map(s, qi, pi, bt_ref, cl_ref)[:3]

    def q_map(s, qi, pi, bt_ref, cl_ref):
        return (s, qi, 0, 0)

    q_spec = pl.BlockSpec((1, bq, nh, d), q_map, memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, bs, g, d), page_map,
                           memory_space=pltpu.VMEM)
    sc_spec = pl.BlockSpec((1, bs, g), scale_map,
                           memory_space=pltpu.VMEM)
    if quantized:
        kernel = _ragged_kernel_quant
        in_specs = [q_spec, kv_spec, sc_spec, kv_spec, sc_spec]
        operands = (q, k_pages, k_scales.astype(jnp.float32),
                    v_pages, v_scales.astype(jnp.float32))
    else:
        kernel = _ragged_kernel_plain
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (q, k_pages, v_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nq, M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, nh, d), q_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((bq * nh, 1), jnp.float32),
            pltpu.VMEM((bq * nh, 1), jnp.float32),
            pltpu.VMEM((bq * nh, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, block_size=bs,
                          block_q=bq, window=window, qpg=qpg),
        name=name + ("_quant" if quantized else ""),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, C, nh, d), q.dtype),
        interpret=_INTERPRET,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      *operands)


# ---------------------------------------------------------------------------
# public entries
# ---------------------------------------------------------------------------

def paged_attention_decode(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Ragged paged attention for one decode token per slot.

    ``q``: [S, nh, d]; pools: [P, bs, g, d] (GQA when g < nh; pass the
    int8 pools plus ``k_scales``/``v_scales`` [P, bs, g] for in-kernel
    dequant); ``block_tables``: [S, M]; ``context_lens``: [S] query
    positions.  Returns [S, nh, d] in ``q.dtype``."""
    assert q.ndim == 3 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    assert q.shape[0] == block_tables.shape[0] == context_lens.shape[0]
    assert (k_scales is None) == (v_scales is None)
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    if not _use_pallas():
        return _reference_paged_attention(
            q, k_pages, v_pages, block_tables, context_lens,
            k_scales, v_scales, softmax_scale, sliding_window)
    return _ragged_call(
        q[:, None], k_pages, v_pages, block_tables, context_lens,
        k_scales, v_scales, scale=softmax_scale, window=sliding_window,
        block_q=1, name="paged_attention_decode")[:, 0]


def paged_attention_prefill(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    block_q: Optional[int] = None,
) -> jax.Array:
    """Ragged paged attention for one prefill chunk per slot.

    ``q``: [S, C, nh, d] — C query tokens per slot sitting at absolute
    positions ``context_lens[s] .. context_lens[s]+C-1`` (their K/V must
    already be scattered into the pools, as the transformer's paged
    branch does before the read).  Row ``j`` attends the full paged
    history plus its own causal prefix of the chunk; padded tail rows of
    a short final chunk compute garbage-in-garbage-out exactly like the
    XLA branch (the engine only reads the last valid row's logits).
    Returns [S, C, nh, d] in ``q.dtype``."""
    assert q.ndim == 4 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    assert q.shape[0] == block_tables.shape[0] == context_lens.shape[0]
    assert (k_scales is None) == (v_scales is None)
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    if not _use_pallas():
        return _reference_paged_prefill(
            q, k_pages, v_pages, block_tables, context_lens,
            k_scales, v_scales, softmax_scale, sliding_window)
    C = q.shape[1]
    bq = min(block_q or _PREFILL_BLOCK_Q, C)
    while C % bq:       # q-blocks must tile the chunk exactly; static
        bq -= 1         # (power-of-two chunks keep the full block size)
    return _ragged_call(
        q, k_pages, v_pages, block_tables, context_lens,
        k_scales, v_scales, scale=softmax_scale, window=sliding_window,
        block_q=bq, name="paged_attention_prefill")
