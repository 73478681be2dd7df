"""The power-retention mixer (the ``retention`` layer type; Manifest AI's
``brumby``, arXiv:2507.04239), a sibling of ``models/mamba.py`` and
``models/short_conv.py``.

Beyond the reference, which has no such layer.  Attention's projections,
per-head norms and rotation (``transformer.qkv_heads``: the same
function an ``attention`` layer calls) over NO key and NO value kept: a
token's mixer output weighs the tokens before it by a POWER of ``q . k``
under a gate's decay, key-value head g and its query heads h:

    a_t,g = logsigmoid(u_t W_g)                  one log-gate a key-value
                                                 head a token, float32
    w_tj  = exp(sum_{l=j+1..t} a_l,g) (q_t,h . k_j,g)^2          j <= t
    o_t,h = sum_j w_tj v_j,g / sum_j w_tj

which is, exactly, a recurrence over ``phi`` with ``phi(x) . phi(y) =
(x . y)^2``, so what a request keeps from token to token is ONE state a
key-value head a layer, whatever its length:

    S_t = exp(a_t) S_{t-1} + phi(k_t) v_t^T      z_t = exp(a_t) z_{t-1} + phi(k_t)
    o_t,h = phi(q_t,h)^T S_t / phi(q_t,h)^T z_t

THE LAYOUT OF ``phi`` (the model leaves it free; this is the program's):
``head_dim / 2 + 1`` ROTATIONS of ``head_dim`` products each,
``phi(x)[o, a] = c_o x[a] x[(a + o) mod d]`` with ``c_0 = 1``, ``c_o =
sqrt 2`` for ``0 < o < d / 2`` and ``c_{d/2} = 1``: every unordered pair
``{a, b}`` lies at rotation ``(b - a) mod d`` or its complement once,
and at ``d / 2`` twice at weight 1, so the inner product is ``sum_{a,b}
x_a x_b y_a y_b``.  8,320 rows at ``d`` 128 where the least a symmetric
square takes is 8,256 (0.8% more state), and every row of ``phi`` is a
lane-rotation of its vector times the vector: nothing is gathered, in
XLA or in the step's kernel.  The state of a key-value head is
``[rotations, d (value), d (a)]`` float32, a ``[value, a]`` tile a
rotation, the normaliser ``[rotations, d]``
(``ops/paged_kv.py``: ``ret_state``, ``ret_sum``; 34.3 MB a layer a
request at 8 heads of 128).

:func:`retention_mixer` is ONE function in two forms, as its siblings
are:

* a **chunk** ``[b, n, h]`` from a given ``(S, z)`` (a prefill chunk of
  the serving engine; the cache-less forward, from zeros), scope
  ``retention_chunk``, ON TWO PATHS as the step is: under a cache whose
  resolved ``kernel`` is ``'pallas'`` (the engine's ``prefill_kernel``:
  one device, Pallas available) ONE kernel that walks the chunk's blocks
  over the slot's state where it lies and forms ``phi`` in VMEM
  (``PagedKVCache.chunk_retention``, ``ops/pallas/retention_chunk.py``,
  launched as ``retention_state_chunk``); everywhere else (no cache:
  tests, ``verify_correctness.py``, whatever differentiates through the
  model; a cache whose ``kernel`` is ``'xla'``: the CPU, a program
  partitioned over several devices) :func:`retention_chunk` in
  ``jax.numpy`` between ``read_state`` and ``write_state``, which is
  also what the kernel's tests compare against.  The algebra and the
  rounding points are the same on both: BLOCKS OF :data:`BLOCK` (128)
  ROWS; inside a block ``(q k^T)^2`` under the causal mask and the
  gates' decay ``exp(A_t - A_j)`` (``A`` the running sum of ``a`` inside
  the block in float32: differences, never a product of ``exp``s),
  across blocks ``phi(q)^T S`` and ``S <- exp(A_end) S + sum_j exp(A_end
  - A_j) phi(k_j) v_j^T``, the blocks in order (XLA's form a
  ``lax.scan`` so that ``phi`` of ONE block is live at a time, the
  kernel's the innermost axis of its grid); numerator and normaliser are
  carried together and divided once.  ``phi``'s products are taken in
  float32, rounded once to the compute dtype a rotation at a time
  (``_phi_held``; ``short_conv._held``: a rounding no fusion drops; the
  kernel's cast, which Mosaic does as given) for the MXU, which reads
  ``S`` and ``z`` in the compute dtype too, and accumulated in float32.
  Exact under padding: a token past a row's ``valid_len`` has ``a = 0``
  and ``k = 0``, so it neither decays nor adds, and an idle row keeps
  its own state;
* a **step** ``[S, 1, h]`` (the decode program), scope
  ``retention_step``: ``PagedKVCache.step_retention``, which on the
  ``'pallas'`` path is ONE in-place kernel over the live rows
  (``ops/pallas/retention_step.py``, launched as
  ``retention_state_step``) and elsewhere the same arithmetic in
  ``jax.numpy`` (``dense_retention_step``), float32 throughout.

The gate (the projection, ``logsigmoid``, the running sums) is scope
``retention_gate``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import TransformerConfig
from megatron_llm_tpu.models.short_conv import _held
from megatron_llm_tpu.ops.pallas import retention_chunk as _chunk_kernel
from megatron_llm_tpu.ops.pallas import retention_step as _step
from megatron_llm_tpu.parallel.layers import (
    init_linear_params,
    init_method_for,
)

# the power of ``q . k`` a token is weighted by: p = 2 of arXiv:2507.04239,
# what the released models and kernels run (even, so no weight is
# negative).  A constant, not data: ``phi``, the state's rows, the kernel
# and the reference are all the SQUARE's; another power is another state
DEGREE = 2

# rows of a chunk's block: the quadratic form is BLOCK x BLOCK a head,
# phi of a block's queries [BLOCK, heads, rotations, d] (a chunk of 512 is
# four blocks; what the model leaves free, so no flag); 128, the chunk's
# kernel's and XLA's form's alike
BLOCK = _chunk_kernel.BLOCK


def init_retention_params(key, cfg: TransformerConfig, dtype):
    """An attention layer's leaves (``query_key_value`` packed by group,
    ``dense``, the per-head norms' scales: ``init_attention_params``) and
    ``gate`` [hidden, key-value heads], drawn as any projection."""
    from megatron_llm_tpu.models.transformer import init_attention_params

    ka, kg = jax.random.split(key)
    params = init_attention_params(ka, cfg, dtype)
    params["gate"] = init_linear_params(
        kg, cfg.hidden_size, cfg.num_query_groups, bias=False,
        init_method=init_method_for(cfg), dtype=dtype)
    return params


def _phi_held(x, cdtype, scale=None):
    """``phi(x)`` (each row's times ``scale`` [...]) as ``cdtype`` holds
    it, ROUNDED A ROTATION AT A TIME as it is formed: a block's ``phi`` is
    written once, in the compute dtype (the float32 products of 128 rows
    of 40 heads are 170 MB, and were written and read back twice a block:
    a quarter of a chunk's 73 ms; chip run, PR 54)."""
    x = x.astype(jnp.float32)
    first = x if scale is None else x * scale[..., None]
    return jnp.stack(
        [_held(first * (c * jnp.roll(x, -o, axis=-1)), cdtype)
         for o, c in enumerate(_step.phi_weights(x.shape[-1]))], axis=-2)


def retention_chunk(q, k, v, a, S, z, cdtype):
    """The chunk form.  ``q`` [b, n, g, r, d] (a key-value head's ``r``
    query heads side by side), ``k``, ``v`` [b, n, g, d], ``a`` [b, n, g]
    float32 log-gates (0 and ``k`` 0 at a token that is not real), ``S``
    [b, g, O, d, d] and ``z`` [b, g, O, d] float32 as the chunk finds
    them.  Returns numerator [b, n, g, r, d] and normaliser [b, n, g, r]
    in float32 and the state after the last real token."""
    b, n, g, r, d = q.shape
    Q = min(BLOCK, n)
    pad = -n % Q
    if pad:
        # a token with a = 0 and k = 0 changes nothing
        q, k, v, a = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] *
                              (x.ndim - 2)) for x in (q, k, v, a))
    c = (n + pad) // Q
    f32 = jnp.float32

    def blocks(x):
        return jnp.moveaxis(x.reshape((b, c, Q) + x.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]

    def block(carry, xs):
        S, z = carry
        qb, kb, vb, ab = xs                     # [b, Q, g, (r,) d], [b, Q, g]
        A = jnp.cumsum(ab, axis=1)                          # [b, Q, g]
        # inside the block: (q . k)^2 under the mask and the decay
        qk = jnp.einsum("btgrd,bsgd->btsgr", qb, kb,
                        preferred_element_type=f32)
        seg = A[:, :, None, :] - A[:, None, :, :]           # [b, t, s, g]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        w = _held(jnp.square(qk) * decay[..., None], cdtype)
        num = jnp.einsum("btsgr,bsgd->btgrd", w, vb.astype(cdtype),
                         preferred_element_type=f32)
        den = w.astype(f32).sum(axis=2)                     # [b, t, g, r]
        # across blocks: phi(q)^T S and phi(q) . z under exp(A_t)
        pq = _phi_held(qb, cdtype)                          # [b,t,g,r,O,d]
        before = jnp.exp(A)[..., None]                      # [b, t, g, 1]
        num = num + before[..., None] * jnp.einsum(
            "btgroa,bgoda->btgrd", pq, S.astype(cdtype),
            preferred_element_type=f32)
        den = den + before * jnp.einsum(
            "btgroa,bgoa->btgr", pq, z.astype(cdtype),
            preferred_element_type=f32)
        # the state at the block's end
        to_end = jnp.exp(A[:, -1:, :] - A)                  # [b, Q, g]
        pk = _phi_held(kb, cdtype, to_end)                  # [b,s,g,O,d]
        kept = jnp.exp(A[:, -1, :])                         # [b, g]
        S = kept[..., None, None, None] * S + jnp.einsum(
            "bsgoa,bsgd->bgoda", pk, vb.astype(cdtype),
            preferred_element_type=f32)
        z = kept[..., None, None] * z + pk.astype(f32).sum(axis=1)
        return (S, z), (num, den)

    (S, z), (num, den) = jax.lax.scan(
        block, (S.astype(f32), z.astype(f32)),
        (blocks(q), blocks(k), blocks(v), blocks(a)))

    def whole(x):
        return jnp.moveaxis(x, 0, 1).reshape((b, c * Q) + x.shape[3:])[:, :n]

    return whole(num), whole(den), S, z


def retention_mixer(h: jax.Array, params, cfg: TransformerConfig, *,
                    freqs=None, position_ids=None, kv_cache=None):
    """``h`` [b, n, hidden] (the layer's normed input) -> the mixer's
    output [b, n, hidden]; with ``kv_cache`` (a ``PagedKVCache`` of the
    ``STATE`` group: ``ops/paged_kv.py``) also the cache as the call
    leaves it.  ``n == 1`` under a cache is the STEP, anything else the
    CHUNK (module docstring); no cache is a chunk from zeros in which
    every token is real."""
    from megatron_llm_tpu.models.transformer import qkv_heads
    from megatron_llm_tpu.ops.paged_kv import PagedKVCache
    from megatron_llm_tpu.parallel.layers import row_parallel_linear

    if kv_cache is not None and not isinstance(kv_cache, PagedKVCache):
        raise NotImplementedError(
            "power-retention layers ('retention') run through the serving "
            "engine's state group or the plain forward, not the legacy "
            "decode caches")
    b, n, _ = h.shape
    cd = cfg.compute_jnp_dtype
    nh, g, d = cfg.num_attention_heads, cfg.num_query_groups, cfg.head_dim
    r, O = nh // g, _step.rotations(d)

    q, k, v, _, _ = qkv_heads(h, params, cfg, freqs=freqs,
                              position_ids=position_ids,
                              layer_type="retention")
    with jax.named_scope("retention_gate"):
        a = jax.nn.log_sigmoid(
            (h.astype(cd) @ params["gate"]["kernel"].astype(cd)
             ).astype(jnp.float32))                         # [b, n, g]
    q = q.reshape(b, n, g, r, d)
    step = kv_cache is not None and n == 1
    if step:
        with jax.named_scope("retention_step"):
            # the cache advances its own state (the kernel in place, or
            # every row read and put back: PagedKVCache.step_retention)
            num, den, kv_cache = kv_cache.step_retention(
                q[:, 0], k[:, 0], v[:, 0], a[:, 0])
            live = (kv_cache.valid_lens > 0)[:, None, None]
            out = num / jnp.where(live, den, 1.0)[..., None]
            out = out[:, None]
            kv_cache = kv_cache.write_state()
    else:
        with jax.named_scope("retention_chunk"):
            in_kernel = kv_cache is not None and kv_cache.kernel == "pallas"
            if kv_cache is None:
                S = jnp.zeros((b, g, O, d, d), jnp.float32)
                z = jnp.zeros((b, g, O, d), jnp.float32)
                valid = jnp.full((b,), n, jnp.int32)
            else:
                valid = kv_cache.valid_lens
                if not in_kernel:
                    S, z = kv_cache.read_state()
            live = (jnp.arange(n)[None, :] < valid[:, None])[..., None]
            if in_kernel:
                # the cache advances its own state, where it lies
                # (PagedKVCache.chunk_retention)
                num, den, kv_cache = kv_cache.chunk_retention(q, k, v, a, cd)
                kv_cache = kv_cache.write_state()
            else:
                num, den, S, z = retention_chunk(
                    q, jnp.where(live[..., None], k, jnp.zeros((), k.dtype)),
                    v, jnp.where(live, a, 0.0), S, z, cd)
            out = num / jnp.where(live[..., None], den, 1.0)[..., None]
            if kv_cache is not None and not in_kernel:
                kv_cache = kv_cache.write_state(S, z)
    out = row_parallel_linear(
        out.astype(cd).reshape(b, n, nh * d), params["dense"],
        in_logical="heads", compute_dtype=cd)
    if kv_cache is not None:
        return out, kv_cache
    return out
