"""Flash attention for TPU (Pallas Mosaic kernels).

Replaces the reference's FlashAttention-2 dependency
(``megatron/model/transformer.py:524-553``), including Mistral's
sliding-window ``window_size`` and GQA/MQA head grouping.

Public entry ``flash_attention(q, k, v, ...)`` with layout
[b, s, heads, d] (batch-major, matching the rest of the framework).

Kernel structure (standard online-softmax tiling):

* forward: grid (batch, q_head, q_blocks, k_blocks), k innermost —
  sequential on TPU, so fp32 scratch (m, l, acc) carries across k blocks;
  fully-masked blocks (beyond causal diagonal / outside sliding window)
  are skipped with ``pl.when``.  Emits O and the per-row logsumexp L for
  the backward pass.  Per-row stats (L, delta) live in lane-broadcast
  ``[..., s, LANES]`` fp32 arrays so every BlockSpec keeps a Mosaic-legal
  (8, 128) trailing tile — a ``(1, 1, bq)`` row-vector out-spec does NOT
  lower on TPU (sublane block 1 over the head axis violates tiling).
* backward: two kernels — dQ (grid over q blocks, k innermost) and
  dK/dV (grid over k blocks, q innermost), both using the saved L and the
  delta = rowsum(dO * O) trick, computing p = exp(s - L) without
  re-running softmax reductions.  GQA: dK/dV are produced per *query*
  head and group-summed outside the kernel.

Dispatch: TPU backend -> kernels; otherwise -> jnp reference math
(identical numerics up to fp associativity).  Interpret-mode tests run the
kernels on CPU.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.softmax import causal_mask, sliding_window_mask

_INTERPRET = False
# Measured on TPU v5e (round 3, llama-400M, seq 2048, bf16): 128x128 blocks
# give 0.17 MFU, 512x512 0.37, 1024x1024 0.39 — the (qi, ki) grid overhead
# and per-block DMA dominate at small tiles.  1024 blocks fit VMEM at
# d=128 (4 MB fp32 score tile) and are clamped to the sequence length for
# short inputs; 2048 tiles fail to compile (scoped-vmem OOM).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
# trailing lane width for per-row stats (LSE, delta): Mosaic requires the
# minor-most block dim to be a multiple of 128 (or the full array dim), so
# row stats are stored value-broadcast across a 128-lane axis.
LANES = 128


def _use_pallas() -> bool:
    from megatron_llm_tpu.ops.pallas import pallas_backend_available

    return _INTERPRET or pallas_backend_available()


# ---------------------------------------------------------------------------
# reference math (non-TPU fallback)
# ---------------------------------------------------------------------------

def _reference_attention(q, k, v, causal, sliding_window, softmax_scale):
    b, sq, nh, d = q.shape
    ng = k.shape[2]
    qpg = nh // ng
    sk = k.shape[1]
    qg = q.reshape(b, sq, ng, qpg, d)
    scores = jnp.einsum("bsgpd,btgd->bgpst", qg, k).astype(jnp.float32)
    scores = scores * softmax_scale
    if causal:
        if sliding_window is not None:
            mask = sliding_window_mask(sq, sk, sliding_window)
        else:
            mask = causal_mask(sq, sk)
        scores = jnp.where(mask[None, None, None].astype(bool), NEG_INF,
                           scores)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    ctx = jnp.einsum("bgpst,btgd->bsgpd", probs, v)
    return ctx.reshape(b, sq, nh, d)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, block_q, block_k, causal, window, kv_len, q_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level skip test (host-static grid; runtime predicate)
    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
    if window is not None:
        run = run & (k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        # sanitize padded rows (pallas block padding is undefined memory;
        # NaNs there would poison the whole block through the matmuls)
        k_row_valid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < kv_len
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, d]
        k = jnp.where(k_row_valid, k_ref[0, 0].astype(jnp.float32), 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # [bq, bk]

        q_ids = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (k_ids < kv_len) & (q_ids < q_len)
        if causal:
            mask &= k_ids <= q_ids
        if window is not None:
            mask &= k_ids > q_ids - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:]                             # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = jnp.where(k_row_valid, v_ref[0, 0].astype(jnp.float32), 0.0)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:]                                  # [bq, 1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_scr[:] + jnp.log(l_safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse, (lse.shape[0], LANES))


def _fwd_call(q, k, v, *, scale, causal, window, block_q, block_k):
    """q [b, nh, sq, d]; k, v [b, ng, sk, d] -> (o, lse)."""
    b, nh, sq, d = q.shape
    ng, sk = k.shape[1], k.shape[2]
    qpg = nh // ng
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(sk, bk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=bq, block_k=bk,
        causal=causal, window=window, kv_len=sk, q_len=sq,
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(b, nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, qi, ki: (bb, h // qpg, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, qi, ki: (bb, h // qpg, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda bb, h, qi, ki: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, nh, sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, scale, block_q, block_k, causal, window, kv_len,
                   q_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
    if window is not None:
        run = run & (k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        k_row_valid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < kv_len
        q_row_valid = (q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)) < q_len
        q = jnp.where(q_row_valid, q_ref[0, 0].astype(jnp.float32), 0.0)
        k = jnp.where(k_row_valid, k_ref[0, 0].astype(jnp.float32), 0.0)
        v = jnp.where(k_row_valid, v_ref[0, 0].astype(jnp.float32), 0.0)
        do = jnp.where(q_row_valid, do_ref[0, 0].astype(jnp.float32), 0.0)
        # stats arrive lane-broadcast [bq, LANES]; any lane reduction
        # recovers the row value (max also tolerates padded-row garbage)
        lse = jnp.where(q_row_valid,
                        jnp.max(lse_ref[0, 0], axis=-1, keepdims=True), 0.0)
        delta = jnp.where(q_row_valid,
                          jnp.max(delta_ref[0, 0], axis=-1, keepdims=True),
                          0.0)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_ids = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (k_ids < kv_len) & (q_ids < q_len)
        if causal:
            mask &= k_ids <= q_ids
        if window is not None:
            mask &= k_ids > q_ids - window
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[:] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, block_q, block_k, causal, window, kv_len,
                    q_len):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
    if window is not None:
        run = run & (k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        k_row_valid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < kv_len
        q_row_valid = (q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)) < q_len
        q = jnp.where(q_row_valid, q_ref[0, 0].astype(jnp.float32), 0.0)
        k = jnp.where(k_row_valid, k_ref[0, 0].astype(jnp.float32), 0.0)
        v = jnp.where(k_row_valid, v_ref[0, 0].astype(jnp.float32), 0.0)
        do = jnp.where(q_row_valid, do_ref[0, 0].astype(jnp.float32), 0.0)
        # stats arrive lane-broadcast [bq, LANES]; any lane reduction
        # recovers the row value (max also tolerates padded-row garbage)
        lse = jnp.where(q_row_valid,
                        jnp.max(lse_ref[0, 0], axis=-1, keepdims=True), 0.0)
        delta = jnp.where(q_row_valid,
                          jnp.max(delta_ref[0, 0], axis=-1, keepdims=True),
                          0.0)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_ids = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (k_ids < kv_len) & (q_ids < q_len)
        if causal:
            mask &= k_ids <= q_ids
        if window is not None:
            mask &= k_ids > q_ids - window
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)        # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        ds = p * (dp - delta)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bk, d]

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                      *, scale, block_q, block_k, causal, window, kv_len,
                      q_len):
    """Single-pass backward: one sweep of the (ki, qi) block grid computes
    dq, dk, dv together, sharing the s = q k^T recompute and the
    dp = do v^T matmul that the two-kernel structure (below) performs
    twice — 5 block matmuls instead of 7.

    dq accumulation: the dq output block is the FULL [sq, d] fp32 slab
    per (b, h), whose index map ignores (ki, qi) — consecutive revisits
    keep it VMEM-resident across the whole sweep, so the row slice for
    each qi accumulates in place with no HBM round trip; it is written
    back once when (b, h) advances."""
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

    @pl.when(qi == 0)
    def _init_kv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
    if window is not None:
        run = run & (k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        k_row_valid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < kv_len
        q_row_valid = (q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)) < q_len
        q = jnp.where(q_row_valid, q_ref[0, 0].astype(jnp.float32), 0.0)
        k = jnp.where(k_row_valid, k_ref[0, 0].astype(jnp.float32), 0.0)
        v = jnp.where(k_row_valid, v_ref[0, 0].astype(jnp.float32), 0.0)
        do = jnp.where(q_row_valid, do_ref[0, 0].astype(jnp.float32), 0.0)
        lse = jnp.where(q_row_valid,
                        jnp.max(lse_ref[0, 0], axis=-1, keepdims=True), 0.0)
        delta = jnp.where(q_row_valid,
                          jnp.max(delta_ref[0, 0], axis=-1, keepdims=True),
                          0.0)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_ids = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (k_ids < kv_len) & (q_ids < q_len)
        if causal:
            mask &= k_ids <= q_ids
        if window is not None:
            mask &= k_ids > q_ids - window
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)         # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        ds = p * (dp - delta)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bk, d]
        rows = pl.ds(q_start, block_q)
        dq_ref[0, 0, rows, :] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_call(q, k, v, do, lse, delta, *, scale, causal, window,
                    bq, bk, nq, nk):
    b, nh, sq, d = q.shape
    ng, sk = k.shape[1], k.shape[2]
    qpg = nh // ng
    kw = dict(scale=scale, block_q=bq, block_k=bk, causal=causal,
              window=window, kv_len=sk, q_len=sq)
    dq, dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, **kw),
        name="flash_attention_bwd_fused",
        grid=(b, nh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, ki, qi: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, ki, qi: (bb, h // qpg, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, ki, qi: (bb, h // qpg, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, ki, qi: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda bb, h, ki, qi: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda bb, h, ki, qi: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            # full-seq dq slab; index map ignores (ki, qi) -> VMEM-resident
            # for the whole (b, h) sweep (see kernel docstring)
            pl.BlockSpec((1, 1, sq, d), lambda bb, h, ki, qi: (bb, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, ki, qi: (bb, h, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, ki, qi: (bb, h, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, sk, d), q.dtype),
            jax.ShapeDtypeStruct((b, nh, sk, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(q, k, v, do, lse, delta)
    dk = dk_h.reshape(b, ng, qpg, sk, d).sum(axis=2)
    dv = dv_h.reshape(b, ng, qpg, sk, d).sum(axis=2)
    return dq.astype(q.dtype), dk, dv


# The fused single-pass backward is the default; the two-kernel structure
# below takes what it cannot: partial trailing blocks, and sequences whose
# dq slab outgrows FUSED_BWD_MAX_SLAB_BYTES.
FUSED_BACKWARD = True
# The fused kernel keeps the whole [sq, d] fp32 dq slab VMEM-resident; the
# round-3 tile sweep put 1024x1024 score tiles near the scoped-vmem limit,
# so cap the slab (4 MB = seq 8192 at d 128) and route longer sequences to
# the two-kernel structure instead of risking a compile-time OOM at
# exactly the long-context lengths.
FUSED_BWD_MAX_SLAB_BYTES = 4 << 20
# The fused kernel's own block sizes.  They are SMALLER than the
# two-kernel 1024 defaults because its scoped-vmem working set carries
# four bq x bk fp32 score-tile intermediates (s, p, dp, ds) PLUS the
# full-seq dq slab: at 1024x1024 that is ~15 MB of tiles before the slab
# and the real compiler rejects it (16.05 MB needed vs the 16 MB
# scoped-vmem limit at seq 2048, worse at longer seq; compiled at
# commit `128e754`, not re-measured).  512x512 tiles cost 4 MB total,
# leaving room for the slab at every supported length.  On one v5e
# fused@512 tied two-kernel@1024 at seq 2048 (commit `128e754`, not
# re-measured).
FUSED_BLOCK_Q = 512
FUSED_BLOCK_K = 512


def _bwd_call(q, k, v, o, lse, do, *, scale, causal, window,
              block_q, block_k):
    b, nh, sq, d = q.shape
    ng, sk = k.shape[1], k.shape[2]
    qpg = nh // ng
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq = pl.cdiv(sq, bq)
    nk = pl.cdiv(sk, bk)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))

    # caller block sizes act as an upper bound (explicit tuning hints,
    # e.g. tests at 64); the fused defaults shrink the usual 1024s to a
    # scoped-vmem-safe size
    fbq = min(FUSED_BLOCK_Q, bq)
    fbk = min(FUSED_BLOCK_K, bk)
    if (FUSED_BACKWARD and sq % fbq == 0 and sk % fbk == 0
            and sq * d * 4 <= FUSED_BWD_MAX_SLAB_BYTES):
        # full blocks only: the fused kernel's in-place row-slice
        # accumulation into the dq slab assumes every q block is complete
        return _bwd_fused_call(
            q, k, v, do, lse, delta, scale=scale, causal=causal,
            window=window, bq=fbq, bk=fbk,
            nq=pl.cdiv(sq, fbq), nk=pl.cdiv(sk, fbk))

    kw = dict(scale=scale, block_q=bq, block_k=bk, causal=causal,
              window=window, kv_len=sk, q_len=sq)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        name="flash_attention_bwd_dq",
        grid=(b, nh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, qi, ki: (bb, h // qpg, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, qi, ki: (bb, h // qpg, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda bb, h, qi, ki: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda bb, h, qi, ki: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, h, qi, ki: (bb, h, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, nh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_INTERPRET,
    )(q, k, v, do, lse, delta)

    # dk/dv per query head, group-summed afterwards (GQA)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        name="flash_attention_bwd_dkv",
        grid=(b, nh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, ki, qi: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, ki, qi: (bb, h // qpg, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, ki, qi: (bb, h // qpg, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, ki, qi: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda bb, h, ki, qi: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, LANES),
                         lambda bb, h, ki, qi: (bb, h, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, ki, qi: (bb, h, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, ki, qi: (bb, h, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, sk, d), q.dtype),
            jax.ShapeDtypeStruct((b, nh, sk, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(q, k, v, do, lse, delta)

    dk = dk_h.reshape(b, ng, qpg, sk, d).sum(axis=2)
    dv = dv_h.reshape(b, ng, qpg, sk, d).sum(axis=2)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom VJP over [b, s, h, d] layout)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, scale, block_q, block_k):
    o, _ = _fwd_call(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
    )
    return jnp.swapaxes(o, 1, 2)


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_k):
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    o, lse = _fwd_call(qt, kt, vt, scale=scale, causal=causal, window=window,
                       block_q=block_q, block_k=block_k)
    return jnp.swapaxes(o, 1, 2), (qt, kt, vt, o, lse)


def _flash_bwd(causal, window, scale, block_q, block_k, res, g):
    qt, kt, vt, o, lse = res
    do = jnp.swapaxes(g, 1, 2)
    dq, dk, dv = _bwd_call(qt, kt, vt, o, lse, do, scale=scale,
                           causal=causal, window=window,
                           block_q=block_q, block_k=block_k)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """q: [b, s, nh, d]; k, v: [b, s, ng, d] (GQA when ng < nh).

    block_q/block_k default to the module-level DEFAULT_BLOCK_Q/K *at call
    time* so benchmarks and configs can retune them without re-importing.
    """
    if block_q is None:
        block_q = DEFAULT_BLOCK_Q
    if block_k is None:
        block_k = DEFAULT_BLOCK_K
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    if not _use_pallas():
        return _reference_attention(q, k, v, causal, sliding_window,
                                    softmax_scale)
    return _flash(q, k, v, causal, sliding_window, softmax_scale,
                  block_q, block_k)


def sharded_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """``flash_attention`` under an active device mesh.

    GSPMD cannot auto-partition Mosaic custom calls ("Mosaic kernels
    cannot be automatically partitioned" — surfaced the moment AOT
    compiles engaged the real kernels, round 5), so under a mesh the
    pallas call must run inside an explicit ``shard_map``.  Attention is
    batch-local and head-local, so the manual region maps batch over dp
    and heads over tp with no collectives: each device runs the kernel
    on its local [b/dp, s, nh/tp, d] slab.  GQA kv heads shard over tp
    when divisible; MQA (ng=1) replicates kv, which preserves the local
    q-heads-per-group ratio.  Falls back to the plain call when no mesh
    axis actually shards the inputs.  Nests inside the pipeline engines'
    pp-manual regions the same way ring attention does
    (``topology.nesting_mesh`` semantics: the abstract mesh, naming only
    the axes still automatic).
    """
    kw = dict(causal=causal, sliding_window=sliding_window,
              softmax_scale=softmax_scale, block_q=block_q,
              block_k=block_k)
    if not _use_pallas():
        # XLA fallback attention partitions automatically; no wrapper
        return flash_attention(q, k, v, **kw)

    from jax.sharding import PartitionSpec as P

    from megatron_llm_tpu import topology

    if not isinstance(q, jax.core.Tracer):
        # eager call (no jit): subset-manual shard_map needs a tracing
        # context, and eager arrays are device-local anyway
        return flash_attention(q, k, v, **kw)

    mesh, manual = topology.current_mesh_and_manual()
    if mesh is None:
        return flash_attention(q, k, v, **kw)

    b, _, nh, _ = q.shape
    ng = k.shape[2]

    def auto_size(name):
        return (mesh.shape[name]
                if name in mesh.axis_names and name not in manual else 1)

    def usable(name, dim_size):
        return auto_size(name) > 1 and dim_size % mesh.shape[name] == 0

    def xla_fallback():
        # a combo the manual mapping can't express: the raw pallas call
        # would hit the GSPMD 'Mosaic kernels cannot be automatically
        # partitioned' lowering error (the arrays may be sharded even
        # when not evenly divisible), so use partitionable XLA math —
        # q-chunked past the length where the [s, s] score tensor is a
        # compile hazard
        from megatron_llm_tpu.ops.chunked_attention import (
            CHUNKED_ATTENTION_MIN_SEQ,
            chunked_causal_attention,
        )

        if q.shape[1] >= CHUNKED_ATTENTION_MIN_SEQ:
            # chunked path handles causal=False too — the [s, s] score
            # hazard doesn't care about masking
            return chunked_causal_attention(
                q, k, v, causal=causal, sliding_window=sliding_window,
                softmax_scale=softmax_scale)
        return _reference_attention(q, k, v, causal, sliding_window,
                                    softmax_scale
                                    or 1.0 / math.sqrt(q.shape[-1]))

    dp = topology.DP_AXIS if usable(topology.DP_AXIS, b) else None
    tp_q = topology.TP_AXIS if usable(topology.TP_AXIS, nh) else None
    tp_kv = tp_q if (tp_q and ng % mesh.shape[tp_q] == 0) else None
    if dp is None and tp_q is None:
        if auto_size(topology.DP_AXIS) == 1 and \
                auto_size(topology.TP_AXIS) == 1:
            # nothing can shard batch/heads: plain pallas is safe
            return flash_attention(q, k, v, **kw)
        return xla_fallback()  # axes exist but dims don't divide
    if tp_q and tp_kv is None and ng > 1:
        # GQA kv heads not divisible by tp: sharding q but replicating kv
        # would change the local q-per-group ratio — unsupported combo
        return xla_fallback()

    qspec = P(dp, None, tp_q, None)
    kvspec = P(dp, None, tp_kv, None)
    # ALL mesh axes still under GSPMD go manual, not just the ones in the
    # specs: with a subset, the Mosaic call still sits inside an
    # auto-sharding region for the remaining axes and the GSPMD partitioner
    # refuses it even when those axes are size 1 / unused.  An axis an
    # enclosing region already made manual (the train step's dp ranks, a
    # pipeline's pp) is NOT named again: naming it without a spec entry
    # says "the same on every rank of it", and the backward would then
    # average dq/dk/dv over ranks that hold different rows.
    return jax.shard_map(
        lambda ql, kl, vl: flash_attention(ql, kl, vl, **kw),
        mesh=mesh,
        in_specs=(qspec, kvspec, kvspec),
        out_specs=qspec,
        axis_names=set(mesh.axis_names) - manual,
        check_vma=False,
    )(q, k, v)
