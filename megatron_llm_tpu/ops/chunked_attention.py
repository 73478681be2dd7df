"""Q-chunked exact attention — the long-context XLA fallback.

On this stack plain XLA attention cannot compile at seq >= 4096: the
[b, heads, s, s] fp32 score tensor crashed the TPU compiler (seen at
commit `128e754`, not re-measured).  When the Pallas flash kernel is
unavailable (``use_flash_attn=False``, or no Pallas backend), the naive
fallback therefore dies exactly where a fallback is needed most.

This op processes Q in row chunks (the same inner-chunk structure as
``parallel/ring_attention.ring_self_attention``, minus the ring): each
chunk materialises only [b, g, p, qc, sk] scores — full softmax over the
key axis per chunk, no online-softmax carry needed since every chunk
sees all keys.  Q-rows are independent in attention, so the chunking is
exact; each chunk is ``jax.checkpoint``-ed so the backward re-derives
scores per chunk instead of stashing the full score tensor.

Reference behavior being replaced: ``CoreAttention``
(megatron/model/transformer.py:144-277) under FlashAttention-less
configs.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
DEFAULT_Q_CHUNK = 1024
# below this many query rows the plain [s, s] path compiles fine and is
# one fused softmax instead of a scan — no reason to chunk
CHUNKED_ATTENTION_MIN_SEQ = 4096


def chunked_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    q_chunk_size: int = DEFAULT_Q_CHUNK,
) -> jax.Array:
    """q [b, sq, nh, d]; k, v [b, sk, ng, d] (GQA when ng < nh) -> ctx
    [b, sq, nh, d].  Exact (same numerics as the unchunked softmax up to
    fp associativity); supports causal and sliding-window masking but not
    arbitrary masks or dropout (the callers' flash-eligibility conditions,
    models/transformer.py ``attention``)."""
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    b, sq, nh, d = q.shape
    sk, ng = k.shape[1], k.shape[2]
    qpg = nh // ng

    # pad sq up to a chunk multiple instead of hunting for a divisor (a
    # near-prime sq would otherwise degrade to single-row chunks); the pad
    # rows compute garbage attention that is sliced off at the end
    qc = min(q_chunk_size, sq)
    n_qc = -(-sq // qc)
    pad = n_qc * qc - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    k_pos = jnp.arange(sk)

    def chunk(ci):
        q_i = lax.dynamic_slice_in_dim(q, ci * qc, qc, axis=1)
        qg = q_i.reshape(b, qc, ng, qpg, d)
        # native-dtype matmuls with fp32 accumulation (not an input
        # upcast, which would force slow fp32 MXU passes on bf16 inputs)
        scores = jnp.einsum("bsgpd,btgd->bgpst", qg, k,
                            preferred_element_type=jnp.float32)
        scores = scores * softmax_scale
        q_pos = ci * qc + jnp.arange(qc)
        mask = jnp.ones((qc, sk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if sliding_window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - sliding_window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bgpst,btgd->bsgpd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return ctx.reshape(b, qc, nh, d).astype(q.dtype)

    if n_qc == 1:
        out = chunk(jnp.int32(0))
        return out[:, :sq] if pad else out

    _, out = lax.scan(
        lambda _, ci: (None, jax.checkpoint(chunk)(ci)),
        None, jnp.arange(n_qc))
    # out [n_qc, b, qc, nh, d] -> [b, n_qc*qc, nh, d] -> drop pad rows
    out = jnp.moveaxis(out, 0, 1).reshape(b, n_qc * qc, nh, d)
    return out[:, :sq] if pad else out
