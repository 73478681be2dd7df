"""The Mamba-2 state-space mixer (the ``mamba`` layer type of a hybrid).

Beyond the reference, which has no state-space layer.  A token's mixer
output is a recurrence over the tokens before it, carried in a state a
head, so what a request keeps from token to token is not keys and values
but TWO arrays of fixed size a layer, whatever its length:

1. ``[z | xBC | dt] = h W_in`` (no bias): a gate ``z`` of the inner width
   ``n_heads * d_head``, the convolution's channels ``xBC`` (the inner
   width and ``2 * n_groups * d_state``), one ``dt`` a head;
2. ``xBC_t = silu(b + sum_j w[:, j] * xBC_{t - (d_conv - 1) + j})``: a
   causal depthwise convolution, every channel its own ``d_conv`` taps
   and bias; then ``x_t`` [heads, d_head], ``B_t`` and ``C_t`` [groups,
   d_state] (a group's heads share them);
3. ``delta_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``, a scalar
   a head;
4. ``S_t = exp(delta_t A) S_{t-1} + delta_t (x_t outer B_t)``, a state
   ``[d_head, d_state]`` a head; ``y_t = S_t C_t + D x_t``;
5. ``y = RMSNorm_w(y * silu(z))``, the mean square over each of the
   ``n_groups`` groups' channels apart (the whole inner width where
   there is one group) under one weight of the inner width, then
   ``y W_out`` (no bias).

The carried state: the last ``d_conv - 1`` columns of ``xBC`` BEFORE the
convolution, ``[d_conv - 1, conv_dim]`` (compute dtype), and ``S``,
``[heads, d_head, d_state]`` in float32.

:func:`mamba_mixer` is ONE function in two forms, as
``latent_attention`` is:

* a **chunk** ``[b, n, h]`` from a given state (a prefill chunk of the
  serving engine; the cache-less forward, from zeros): step 4 by the
  CHUNKED SCAN (:func:`chunked_scan`, scope ``ssm_scan``): blocks of
  ``mamba_chunk_size`` tokens, inside a block a masked product of
  ``C_t . B_s`` and the decays between s and t, between blocks the state
  handed on.  Products in the compute dtype with float32 accumulation,
  decays and ``S`` in float32.  It is exact under padding: a token past
  a row's ``valid_len`` has ``delta = 0``, so it leaves ``S`` as it is
  and adds nothing, and the convolution's columns are taken at the row's
  last VALID tokens;
* a **step** ``[S, 1, h]`` (the decode program, scope ``ssm_step``): the
  recurrence itself, once.  Where the cache's path is ``'pallas'`` it is
  ONE kernel (``ops/pallas/ssm_step.py``, launched as
  ``ssm_state_step``) that updates the state pool in place and moves the
  live rows only, each row's state read once and written once; the
  ``'xla'`` path (the CPU, a mesh of several devices, and what the
  kernel's tests compare against) reads every row's state, advances it
  and writes every slot back.  ``PagedKVCache.step_state`` is both.

The chunk's scan, the convolution and the projections are XLA's (ROADMAP
S19 has what is left).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import TransformerConfig
from megatron_llm_tpu.ops.layernorm import rms_norm
from megatron_llm_tpu.parallel.layers import (
    init_linear_params,
    init_method_for,
    scaled_init_method_normal,
)


def init_mamba_params(key, cfg: TransformerConfig, dtype):
    """{'in_proj': [h, d_inner + conv_dim + heads] as [z | xBC | dt],
    'conv': {'kernel': [conv_dim, d_conv], 'bias': [conv_dim]},
    'dt_bias', 'A_log', 'D': [heads], 'norm': {'scale': [d_inner]},
    'out_proj': [d_inner, h]}.  ``A`` in [-16, -1] and ``delta`` in
    [0.001, 0.1] at a zero ``dt``, as the published initialisation draws
    them; the convolution as a framework's default does (uniform within
    ``d_conv ** -0.5``)."""
    k_in, k_out, k_conv, k_cb, k_dt, k_a = jax.random.split(key, 6)
    init = init_method_for(cfg)
    out_init = (
        scaled_init_method_normal(cfg.init_method_std, cfg.num_layers)
        if cfg.use_scaled_init_method else init)
    nh, di, cd = cfg.mamba_n_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    bound = cfg.mamba_d_conv ** -0.5
    dt = jnp.exp(jax.random.uniform(k_dt, (nh,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    params = {
        "in_proj": init_linear_params(k_in, cfg.hidden_size, di + cd + nh,
                                      bias=False, init_method=init,
                                      dtype=dtype),
        "conv": {"kernel": jax.random.uniform(
            k_conv, (cd, cfg.mamba_d_conv), jnp.float32, -bound,
            bound).astype(dtype)},
        # softplus's inverse of dt
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            k_a, (nh,), jnp.float32, 1.0, 16.0)).astype(dtype),
        "D": jnp.ones((nh,), dtype),
        "norm": {"scale": jnp.ones((di,), dtype)},
        "out_proj": init_linear_params(k_out, di, cfg.hidden_size,
                                       bias=False, init_method=out_init,
                                       dtype=dtype),
    }
    if cfg.mamba_conv_bias:
        params["conv"]["bias"] = jax.random.uniform(
            k_cb, (cd,), jnp.float32, -bound, bound).astype(dtype)
    return params


def chunked_scan(x: jax.Array, delta: jax.Array, A: jax.Array,
                 B: jax.Array, C: jax.Array, state: jax.Array,
                 block: int):
    """Step 4 over a chunk, by blocks of ``block`` tokens.

    ``x`` [b, n, nh, dh] and ``B``, ``C`` [b, n, g, ds] in the compute
    dtype; ``delta`` [b, n, nh] float32 (0 at a token that is not real);
    ``A`` [nh] float32 (negative); ``state`` [b, nh, dh, ds] float32, the
    state before the chunk's first token.  Returns ``y`` [b, n, nh, dh]
    float32 (WITHOUT the ``D x`` term) and the state after the last token
    whose ``delta`` is not 0.

    With ``a_t = delta_t A`` and ``La`` its running sum inside a block:
    ``y_t = sum_{s <= t} exp(La_t - La_s) (C_t . B_s) delta_s x_s
    + exp(La_t) C_t . S_block`` and ``S_next = exp(La_end) S_block
    + sum_s exp(La_end - La_s) delta_s (x_s outer B_s)``."""
    b, n, nh, dh = x.shape
    g, ds = B.shape[2], B.shape[3]
    Q = min(block, n)
    pad = -n % Q
    if pad:
        # a token with delta 0 changes nothing
        x, delta, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] *
                                  (a.ndim - 2)) for a in (x, delta, B, C))
    c = (n + pad) // Q
    cdtype = x.dtype
    x = x.reshape(b, c, Q, nh, dh)
    delta = delta.reshape(b, c, Q, nh)
    # a group's heads side by side: [.., g, nh / g, ..]
    hg = nh // g
    B = B.reshape(b, c, Q, g, ds)
    C = C.reshape(b, c, Q, g, ds)
    a = delta * A                                           # [b, c, Q, nh]
    La = jnp.cumsum(a, axis=2)
    xd = (x.astype(jnp.float32) * delta[..., None]).astype(cdtype)

    # inside a block: the masked decays between s and t, a head (a
    # group's heads share C_t . B_s)
    seg = La[:, :, :, None, :] - La[:, :, None, :, :]       # [b,c,t,s,nh]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    G = jnp.einsum("bctgn,bcsgn->bctsg", C, B,
                   preferred_element_type=jnp.float32)
    M = (G[..., None] * decay.reshape(b, c, Q, Q, g, hg)).astype(cdtype)
    xd = xd.reshape(b, c, Q, g, hg, dh)
    y = jnp.einsum("bctsgk,bcsgkd->bctgkd", M, xd,
                   preferred_element_type=jnp.float32)

    # what each block adds to the state at its end, and the blocks' ends
    # handed on, in float32
    to_end = jnp.exp(La[:, :, -1:, :] - La).reshape(b, c, Q, g, hg)
    xe = (xd.astype(jnp.float32) * to_end[..., None]).astype(cdtype)
    added = jnp.einsum("bcsgkd,bcsgn->bcgkdn", xe, B,
                       preferred_element_type=jnp.float32)
    whole = jnp.exp(La[:, :, -1, :]).reshape(b, c, g, hg)

    def hand_on(S, blk):
        add, w = blk
        return w[..., None, None] * S + add, S

    last, before = jax.lax.scan(
        hand_on, state.astype(jnp.float32).reshape(b, g, hg, dh, ds),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                     # [b,c,g,k,dh,ds]
    carried = jnp.einsum("bctgn,bcgkdn->bctgkd", C, before.astype(cdtype),
                         preferred_element_type=jnp.float32)
    y = y + carried * jnp.exp(La).reshape(b, c, Q, g, hg)[..., None]
    last = last.reshape(b, nh, dh, ds)
    return y.reshape(b, c * Q, nh, dh)[:, :n], last


def gated_group_norm(y: jax.Array, scale: jax.Array, groups: int,
                     eps: float) -> jax.Array:
    """Step 5's norm of ``y`` [..., d_inner] (float32, already gated):
    each of the ``groups`` groups of ``d_inner / groups`` channels
    divided by its own root mean square, then the weight of the whole
    width.  One group is ``rms_norm`` itself, the same operations."""
    if groups == 1:
        return rms_norm(y, scale, eps=eps)
    grouped = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + eps)
    return grouped.reshape(y.shape) * scale.astype(y.dtype)


def causal_conv_silu(x: jax.Array, conv_state: jax.Array, conv: dict,
                     valid: jax.Array, cd):
    """Step 2: ``silu`` of the causal depthwise convolution of ``x`` [b,
    n, channels] (compute dtype ``cd``) behind the ``K - 1`` columns
    ``conv_state`` [b, K - 1, channels] a row carried in, under ``conv``
    (``kernel`` [channels, K] and a ``bias`` a channel or none), taps
    applied in float32.  Also the columns BEFORE the convolution at each
    row's last ``valid`` tokens, ``ext[valid : valid + K - 1]``: what
    the row carries on (an idle row, ``valid`` 0, keeps its own).  What a
    state-space layer does to ``xBC`` and a delta-rule layer
    (``models/gated_delta.py``) to its queries, keys and values."""
    n, K = x.shape[1], conv["kernel"].shape[1]
    w = conv["kernel"].astype(jnp.float32)                  # [channels, K]
    ext = jnp.concatenate([conv_state.astype(cd), x], axis=1)
    acc = conv.get("bias", jnp.zeros(())).astype(
        jnp.float32) + sum(
        ext[:, j:j + n].astype(jnp.float32) * w[:, j] for j in range(K))
    y = jax.nn.silu(acc).astype(cd)
    new_conv = jax.vmap(lambda e, v: jax.lax.dynamic_slice_in_dim(
        e, v, K - 1, axis=0))(ext, valid)
    return y, new_conv


def mamba_mixer(h: jax.Array, params, cfg: TransformerConfig, *,
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
            "state-space layers ('mamba') run through the serving engine's "
            "state group or the plain forward, not the legacy decode "
            "caches")
    b, n, _ = h.shape
    cd = cfg.compute_jnp_dtype
    nh, dh, ds = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    g, K = cfg.mamba_n_groups, cfg.mamba_d_conv
    di, cdim = cfg.mamba_d_inner, cfg.mamba_conv_dim

    with jax.named_scope("ssm_in_proj"):
        zxbcdt = h.astype(cd) @ params["in_proj"]["kernel"].astype(cd)
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + cdim]
    dt = zxbcdt[..., di + cdim:].astype(jnp.float32)

    if kv_cache is not None:
        conv_state, state = kv_cache.read_state()
        valid = kv_cache.valid_lens
    else:
        conv_state = jnp.zeros((b, K - 1, cdim), cd)
        state = jnp.zeros((b, nh, dh, ds), jnp.float32)
        valid = jnp.full((b,), n, jnp.int32)

    with jax.named_scope("ssm_conv"):
        xBC, new_conv = causal_conv_silu(xBC, conv_state, params["conv"],
                                         valid, cd)

    x = xBC[..., :di].reshape(b, n, nh, dh)
    Bm = xBC[..., di:di + g * ds].reshape(b, n, g, ds)
    Cm = xBC[..., di + g * ds:].reshape(b, n, g, ds)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    delta = jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32))
    live = jnp.arange(n)[None, :] < valid[:, None]
    delta = jnp.where(live[..., None], delta, 0.0)          # [b, n, nh]

    if kv_cache is not None and n == 1:
        with jax.named_scope("ssm_step"):
            d1 = delta[:, 0]                                # [b, nh]
            # the cache advances its own state (the kernel in place, or
            # every row read and put back: PagedKVCache.step_state)
            y, kv_cache = kv_cache.step_state(
                jnp.exp(d1 * A), d1[..., None] * x[:, 0].astype(jnp.float32),
                Bm[:, 0].astype(jnp.float32), Cm[:, 0].astype(jnp.float32))
            y, new_state = y[:, None], None
    else:
        with jax.named_scope("ssm_scan"):
            y, new_state = chunked_scan(x, delta, A, Bm, Cm, state,
                                        cfg.mamba_chunk_size)
    y = y + params["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)

    with jax.named_scope("ssm_gate_norm"):
        y = y.reshape(b, n, di) * jax.nn.silu(z.astype(jnp.float32))
        y = gated_group_norm(y, params["norm"]["scale"], g,
                             cfg.layernorm_epsilon)
    with jax.named_scope("ssm_out_proj"):
        out = y.astype(cd) @ params["out_proj"]["kernel"].astype(cd)
    if kv_cache is not None:
        # the write belongs to the recurrence it ends
        with jax.named_scope("ssm_step" if n == 1 else "ssm_scan"):
            return out, kv_cache.write_state(new_conv, new_state)
    return out
