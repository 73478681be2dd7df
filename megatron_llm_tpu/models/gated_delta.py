"""The gated delta-rule mixer (the ``gated_delta`` layer type; Qwen's
``qwen3_next`` ``Qwen3NextGatedDeltaNet``, its ``linear_attention``
layers), a sibling of ``models/mamba.py``, ``models/short_conv.py`` and
``models/retention.py``.

Beyond the reference, which has no such layer.  A token's mixer output
reads a state a VALUE head that every token before it has rewritten, so
what a request keeps from token to token is two arrays of fixed size a
layer, whatever its length; and unlike its siblings' states, which only
decay and add, this one is UPDATED BY WHAT IT HOLDS:

1. ``[q | k | v | z] = u W_qkvz`` and ``[b | a] = u W_ba`` (no bias):
   ``delta_key_heads`` query and key heads of ``delta_key_dim``,
   ``delta_value_heads`` value heads of ``delta_value_dim`` and a gate
   ``z`` as wide, one ``b`` and one ``a`` a value head;
2. ``[q | k | v] = silu(conv([q | k | v]))``: a causal depthwise
   convolution of ``delta_conv_taps`` taps, every channel its own, no
   bias: ``mamba.causal_conv_silu``, the function a state-space layer
   runs over its ``xBC``;
3. ``q = l2norm(q) / sqrt(d_key)``, ``k = l2norm(k)`` over a head;
   ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``, a
   value head a token in float32, ``g <= 0``; key head j serves value
   heads ``j r .. j r + r - 1``, ``r`` their ratio;
4. a value head's state ``S`` ``[d_key, d_value]`` float32:

       S'  = exp(g_t) S_{t-1}
       d_t = beta_t (v_t - S'^T k_t)     what the state already answers
       S_t = S' + k_t d_t^T              for k_t is taken off
       o_t = S_t^T q_t

5. ``y = RMSNorm_w(o) * silu(z)`` over each head's ``d_value`` under one
   scale of that width (the NORM FIRST, then the gate: Mamba's
   ``gated_group_norm`` takes its input already gated, so it is
   ``rms_norm`` a head here and the gate after), then ``y W_out``.

The carried state: the last ``taps - 1`` columns of ``[q | k | v]``
BEFORE the convolution (compute dtype) and ``S`` ``[value heads, d_key,
d_value]`` float32 (``ops/paged_kv.py``: ``conv_state``,
``delta_state``; 2.1 MB a layer a request at 32 heads of 128 x 128).

:func:`gated_delta_mixer` is ONE function in two forms, as its siblings
are:

* a **chunk** ``[b, n, h]`` from a given ``(S, columns)`` (a prefill
  chunk of the serving engine; the cache-less forward, from zeros),
  scope ``delta_chunk``: step 4 over BLOCKS OF :data:`BLOCK` (64) ROWS
  (:func:`gated_delta_chunk`).  With ``G`` the running sum of ``g``
  inside a block (float32) and ``Gam_tj = exp(G_t - G_j)`` for ``j <=
  t`` (always a difference, never a product of ``exp``s), a block's
  ``d`` solve a UNIT LOWER TRIANGULAR system,

      (I + A) D = diag(beta) (V - (exp(G) * K) S_0),
      A_tj = beta_t Gam_tj (k_t . k_j) for j < t,

  whose left side does not depend on the carried state: so ``(I +
  A)^{-1}`` is applied to ``beta V`` and to ``beta exp(G) K`` for every
  block of the chunk at once (``lax.linalg.triangular_solve``, forward
  substitution in float32), and the blocks are then walked in order
  with three products each: ``D = U - W S_0``, ``o_t = exp(G_t) S_0^T
  q_t + sum_{j<=t} Gam_tj (q_t . k_j) d_j``, ``S_end = exp(G_end) S_0 +
  sum_j exp(G_end - G_j) k_j d_j^T``.  Products are taken in the compute
  dtype and accumulated in float32, each operand ROUNDED to it whatever
  the compiler fuses (``short_conv._held``); ``G``, ``A``, the solve and
  the carried ``S`` are float32.  Exact under padding: a token past a
  row's ``valid_len`` has ``g = 0`` and ``beta = 0``, so it neither
  decays nor writes, the state a row leaves is that at its last VALID
  token, an idle row keeps its own, and the convolution's columns are
  the last VALID ones.  Under a ``PagedKVCache`` whose resolved
  ``kernel`` is ``'pallas'`` (the engine's ``prefill_kernel``: the rule
  ``models/retention.py`` follows) the same algebra, block for block and
  rounding for rounding, is ONE kernel
  (``ops/pallas/delta_chunk.py``, launched as ``delta_state_chunk``)
  that solves a block's systems by blocks on the MXU beside the heads'
  state held in VMEM and writes nothing between the gates and ``o`` to
  HBM; the cache-less forward and an ``'xla'`` cache run
  :func:`gated_delta_chunk`, one algorithm on two back ends chosen by
  what the cache already resolved;
* a **step** ``[S, 1, h]`` (the decode program), scope ``delta_step``:
  ``PagedKVCache.step_delta``, which on the ``'pallas'`` path is ONE
  in-place kernel over the live rows (``ops/pallas/delta_step.py``,
  launched as ``delta_state_step``) and elsewhere the same arithmetic in
  ``jax.numpy`` (``dense_gated_delta_step``), float32 throughout.

Scopes: ``delta_proj`` (the two input projections and ``W_out``),
``delta_conv``, ``delta_gate`` (``beta``, ``g``, the l2 norms),
``delta_chunk``, ``delta_step``, ``delta_norm``.  The projections, the
convolution, the gates and the norm are XLA's; the recurrence is a
kernel in both forms on the ``'pallas'`` path.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import TransformerConfig
from megatron_llm_tpu.models.mamba import causal_conv_silu
from megatron_llm_tpu.models.short_conv import _held
from megatron_llm_tpu.ops.layernorm import rms_norm
from megatron_llm_tpu.ops.pallas.delta_chunk import BLOCK, delta_state_chunk
from megatron_llm_tpu.ops.pallas.delta_step import for_value_heads
from megatron_llm_tpu.parallel.layers import (
    init_linear_params,
    init_method_for,
    scaled_init_method_normal,
)

# the l2 norm's guard, the published kernels': x * rsqrt(sum x^2 + eps)
_L2_EPS = 1e-6
# the half-lives a FRESH model's heads are drawn between, in tokens (at a
# zero ``a``): the published initialiser (``A`` uniform in (0, 16) under
# a ``dt_bias`` of ones) forgets a token in one step, under which no
# control on the state could tell anything; a checkpoint overwrites both
HALF_LIVES = (16.0, 2048.0)


def init_gated_delta_params(key, cfg: TransformerConfig, dtype):
    """{'in_proj': [h, 2 kh dk + 2 hv dv] as [q | k | v | z], 'ba_proj':
    [h, 2 hv] as [b | a], 'conv': {'kernel': [2 kh dk + hv dv, taps]},
    'dt_bias', 'A_log': [hv], 'norm': {'scale': [dv]}, 'out_proj': [hv
    dv, h]}.  ``A`` uniform in [1, 16] as Mamba's, and ``dt_bias`` such
    that a head's decay a token at a zero ``a``, ``exp(-A softplus(
    dt_bias))``, has a half-life drawn log-uniformly over
    :data:`HALF_LIVES`; the convolution as a framework's default draws
    it (uniform within ``taps ** -0.5``)."""
    k_in, k_ba, k_out, k_conv, k_hl, k_a = jax.random.split(key, 6)
    init = init_method_for(cfg)
    out_init = (
        scaled_init_method_normal(cfg.init_method_std, cfg.num_layers)
        if cfg.use_scaled_init_method else init)
    h, hv = cfg.hidden_size, cfg.delta_value_heads
    inner, cdim = hv * cfg.delta_value_dim, cfg.delta_conv_dim
    bound = cfg.delta_conv_taps ** -0.5
    A = jax.random.uniform(k_a, (hv,), jnp.float32, 1.0, 16.0)
    half = jnp.exp(jax.random.uniform(
        k_hl, (hv,), jnp.float32, *(math.log(x) for x in HALF_LIVES)))
    dt = math.log(2.0) / (half * A)
    return {
        "in_proj": init_linear_params(k_in, h, cdim + inner, bias=False,
                                      init_method=init, dtype=dtype),
        "ba_proj": init_linear_params(k_ba, h, 2 * hv, bias=False,
                                      init_method=init, dtype=dtype),
        "conv": {"kernel": jax.random.uniform(
            k_conv, (cdim, cfg.delta_conv_taps), jnp.float32, -bound,
            bound).astype(dtype)},
        # softplus's inverse of dt
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(A).astype(dtype),
        "norm": {"scale": jnp.ones((cfg.delta_value_dim,), dtype)},
        "out_proj": init_linear_params(k_out, inner, h, bias=False,
                                       init_method=out_init, dtype=dtype),
    }


def gated_delta_chunk(q, k, v, g, beta, S, cdtype):
    """Step 4 over a chunk, by blocks of :data:`BLOCK` rows (module
    docstring).  ``q``, ``k`` [b, n, kh, dk] and ``v`` [b, n, hv, dv] in
    the compute dtype; ``g`` and ``beta`` [b, n, hv] float32 (both 0 at a
    token that is not real); ``S`` [b, hv, dk, dv] float32 as the chunk
    finds it.  Returns ``o`` [b, n, hv, dv] float32 and the state after
    the last real token."""
    b, n, kh, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // kh
    Q = min(BLOCK, n)
    pad = -n % Q
    if pad:
        # a token with g = 0 and beta = 0 changes nothing
        q, k, v, g, beta = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] *
                                    (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    c = (n + pad) // Q
    f32 = jnp.float32

    def blocks(x):
        """[b, c * Q, heads, ...] -> [b, c, heads, Q, ...]."""
        return jnp.moveaxis(x.reshape((b, c, Q) + x.shape[2:]), 2, 3)

    qb, kb, vb = blocks(q), blocks(k), blocks(v)            # cdtype
    gb, bb = blocks(g), blocks(beta)                        # [b,c,hv,Q]
    G = jnp.cumsum(gb, axis=-1)
    # Gam_tj = exp(G_t - G_j) at j <= t: differences, masked BEFORE exp
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    Gam = jnp.where(seen, jnp.exp(jnp.where(
        seen, G[..., :, None] - G[..., None, :], 0.0)), 0.0)

    def per_value_head(x):
        """A key head's [b, c, kh, ...] for each of its r value heads."""
        return for_value_heads(x, r, axis=2)

    kk = per_value_head(jnp.einsum("bcjtd,bcjsd->bcjts", kb, kb,
                                   preferred_element_type=f32))
    qk = per_value_head(jnp.einsum("bcjtd,bcjsd->bcjts", qb, kb,
                                   preferred_element_type=f32))
    # (I + A) X = [beta exp(G) K | beta V], A strictly lower: forward
    # substitution in float32, every block of the chunk at once
    A = jnp.where(jnp.tril(seen, -1), bb[..., :, None] * Gam * kk, 0.0)
    kv = per_value_head(kb).astype(f32)                     # [b,c,hv,Q,dk]
    rhs = jnp.concatenate(
        [(bb * jnp.exp(G))[..., None] * kv,
         bb[..., None] * vb.astype(f32)], axis=-1)
    X = jax.lax.linalg.triangular_solve(
        A + jnp.eye(Q, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    W, U = _held(X[..., :dk], cdtype), X[..., dk:]
    M = _held(Gam * qk, cdtype)                             # [b,c,hv,Q,Q]
    before = jnp.exp(G)[..., None]                          # [b,c,hv,Q,1]
    to_end = _held(jnp.exp(G[..., -1:] - G)[..., None] * kv, cdtype)
    kept = jnp.exp(G[..., -1])                              # [b,c,hv]
    qv = per_value_head(qb)

    def block_of(carry, xs):
        S = carry                                           # [b,hv,dk,dv]
        W, U, M, before, to_end, kept, qv = xs
        Sc = _held(S, cdtype)
        D = _held(U - jnp.einsum("bhtk,bhkv->bhtv", W, Sc,
                                 preferred_element_type=f32), cdtype)
        o = before * jnp.einsum("bhtk,bhkv->bhtv", qv, Sc,
                                preferred_element_type=f32)
        o = o + jnp.einsum("bhts,bhsv->bhtv", M, D,
                           preferred_element_type=f32)
        S = kept[..., None, None] * S + jnp.einsum(
            "bhsk,bhsv->bhkv", to_end, D, preferred_element_type=f32)
        return S, o

    S, o = jax.lax.scan(
        block_of, S.astype(f32),
        tuple(jnp.moveaxis(x, 1, 0)
              for x in (W, U, M, before, to_end, kept, qv)))
    # [c, b, hv, Q, dv] -> [b, n, hv, dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, c * Q, hv, dv)
    return o[:, :n], S


def l2norm(x: jax.Array) -> jax.Array:
    """``x`` [..., d] (float32) over the root of its summed squares."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + _L2_EPS)


def query_scale(d_key: int) -> float:
    """What a normed query is multiplied by."""
    return d_key ** -0.5


def gated_norm(o: jax.Array, z: jax.Array, scale: jax.Array,
               eps: float) -> jax.Array:
    """Step 5: ``o`` [..., heads, d_value] (float32) RMSNorm'd a head
    under ``scale``, THEN times ``silu(z)`` [..., heads * d_value]."""
    y = rms_norm(o, scale.astype(jnp.float32), eps=eps)
    return y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))


def gated_delta_mixer(h: jax.Array, params, cfg: TransformerConfig, *,
                      kv_cache=None):
    """``h`` [b, n, hidden] (the layer's normed input) -> the mixer's
    output [b, n, hidden]; with ``kv_cache`` (a ``PagedKVCache`` of the
    ``STATE`` group: ``ops/paged_kv.py``) also the cache as the call
    leaves it.  ``n == 1`` under a cache is the STEP, anything else the
    CHUNK (module docstring); no cache is a chunk from zeros in which
    every token is real."""
    from megatron_llm_tpu.ops.paged_kv import PagedKVCache

    if kv_cache is not None and not isinstance(kv_cache, PagedKVCache):
        raise NotImplementedError(
            "gated delta-rule layers ('gated_delta') run through the "
            "serving engine's state group or the plain forward, not the "
            "legacy decode caches")
    b, n, _ = h.shape
    cd, f32 = cfg.compute_jnp_dtype, jnp.float32
    kh, hv = cfg.delta_key_heads, cfg.delta_value_heads
    dk, dv, K = cfg.delta_key_dim, cfg.delta_value_dim, cfg.delta_conv_taps
    cdim = cfg.delta_conv_dim

    with jax.named_scope("delta_proj"):
        u = h.astype(cd)
        qkvz = u @ params["in_proj"]["kernel"].astype(cd)
        ba = (u @ params["ba_proj"]["kernel"].astype(cd)).astype(f32)
    qkv, z = qkvz[..., :cdim], qkvz[..., cdim:]

    if kv_cache is not None:
        conv_state, state = kv_cache.read_state()
        valid = kv_cache.valid_lens
    else:
        conv_state = jnp.zeros((b, K - 1, cdim), cd)
        state = jnp.zeros((b, hv, dk, dv), f32)
        valid = jnp.full((b,), n, jnp.int32)

    with jax.named_scope("delta_conv"):
        qkv, new_conv = causal_conv_silu(qkv, conv_state, params["conv"],
                                         valid, cd)

    with jax.named_scope("delta_gate"):
        def heads(x):
            return x.reshape(b, n, kh, dk).astype(f32)

        q = _held(l2norm(heads(qkv[..., :kh * dk])) * query_scale(dk), cd)
        k = _held(l2norm(heads(qkv[..., kh * dk:2 * kh * dk])), cd)
        v = qkv[..., 2 * kh * dk:].reshape(b, n, hv, dv)
        live = (jnp.arange(n)[None, :] < valid[:, None])[..., None]
        beta = jnp.where(live, jax.nn.sigmoid(ba[..., :hv]), 0.0)
        g = jnp.where(live, -jnp.exp(params["A_log"].astype(f32))
                      * jax.nn.softplus(
                          ba[..., hv:] + params["dt_bias"].astype(f32)), 0.0)

    if kv_cache is not None and n == 1:
        with jax.named_scope("delta_step"):
            # the cache advances its own state (the kernel in place, or
            # every row read and put back: PagedKVCache.step_delta)
            o, kv_cache = kv_cache.step_delta(q[:, 0], k[:, 0], v[:, 0],
                                              g[:, 0], beta[:, 0])
            o, new_state = o[:, None], None
    else:
        with jax.named_scope("delta_chunk"):
            # one algorithm on two back ends, by what the cache resolved
            in_kernel = kv_cache is not None and kv_cache.kernel == "pallas"
            chunk = delta_state_chunk if in_kernel else gated_delta_chunk
            o, new_state = chunk(q, k, v, g, beta, state, cd)

    with jax.named_scope("delta_norm"):
        y = gated_norm(o, z, params["norm"]["scale"],
                       cfg.layernorm_epsilon)
    with jax.named_scope("delta_proj"):
        out = y.astype(cd) @ params["out_proj"]["kernel"].astype(cd)
    if kv_cache is not None:
        # the write belongs to the recurrence it ends
        with jax.named_scope("delta_step" if n == 1 else "delta_chunk"):
            return out, kv_cache.write_state(new_conv, new_state)
    return out
