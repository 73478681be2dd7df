"""The gated short-convolution mixer (the ``conv`` layer type; LiquidAI's
``lfm2`` / ``lfm2_moe`` ``Lfm2ShortConv``), a sibling of ``models/mamba.py``.

Beyond the reference, which has no such layer.  A token's mixer output
reads the few tokens before it through a causal depthwise convolution and
nothing else, so what a request keeps from token to token is ONE small
array a layer, whatever its length:

1. ``[B | C | X] = u W_in`` (no bias), each the hidden width;
2. ``z = B * X``, elementwise;
3. ``c_t = sum_j w[:, j] * z_{t - (taps - 1) + j}``: every channel its own
   ``conv_taps`` taps (3 as published), causal, zeros before the
   sequence, a bias a channel only with ``cfg.conv_mixer_bias``, and NO
   activation;
4. ``o = (C * c) W_out`` (no bias).

The carried state: the last ``taps - 1`` columns of ``z``, ``[taps - 1,
hidden]`` in the compute dtype (copies of activations; 8 KiB a layer at
the published 2,048 in bf16).  The taps lie in the sublanes of the
state's rows, as Mamba's ``conv_state`` does: a last dimension of 2 would
be laid out at 128 lanes.

:func:`short_conv_mixer` is ONE function in two forms, as ``mamba_mixer``
is:

* a **chunk** ``[b, n, h]`` from a given state (a prefill chunk of the
  serving engine; the cache-less forward, from zeros).  Exact under
  padding: the carried columns are taken at the row's last VALID tokens,
  and an idle row (``valid_lens`` 0) keeps its own;
* a **step** ``[S, 1, h]`` (the decode program): the same three sums over
  the slot's two columns and the token's own.

The taps are applied in float32, the two gates' products are the compute
dtype's, and ROUNDED to it whatever the compiler fuses (``_held``): the
TPU's compiler is allowed excess precision and keeps a bf16 product in
float32 where it fuses it into its consumer, so ``B * X`` and the
convolution's output were rounded in one program and not in another that
traces the same step (the engine's decode step against the same step
without its sampler: 91 of 199 steps seated another expert somewhere in
12 sparse layers, whose routers' margins are a few thousandths; chip run,
PR 51), while the columns a slot carries are always the rounded ones.
Everything is XLA's: at 128 live rows a layer's state is 1 MB read and
1 MB written a step (``short_conv_busy_pct`` says what a kernel would
replace).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import TransformerConfig
from megatron_llm_tpu.parallel.layers import (
    init_linear_params,
    init_method_for,
    scaled_init_method_normal,
)


def init_short_conv_params(key, cfg: TransformerConfig, dtype):
    """{'in_proj': [h, 3h] as [B | C | X], 'conv': {'kernel': [h, taps]
    (, 'bias': [h])}, 'out_proj': [h, h]}; the convolution as a
    framework's default draws it (uniform within ``taps ** -0.5``)."""
    k_in, k_out, k_conv, k_cb = jax.random.split(key, 4)
    init = init_method_for(cfg)
    out_init = (
        scaled_init_method_normal(cfg.init_method_std, cfg.num_layers)
        if cfg.use_scaled_init_method else init)
    h, K = cfg.hidden_size, cfg.conv_taps
    bound = K ** -0.5
    params = {
        "in_proj": init_linear_params(k_in, h, 3 * h, bias=False,
                                      init_method=init, dtype=dtype),
        "conv": {"kernel": jax.random.uniform(
            k_conv, (h, K), jnp.float32, -bound, bound).astype(dtype)},
        "out_proj": init_linear_params(k_out, h, h, bias=False,
                                       init_method=out_init, dtype=dtype),
    }
    if cfg.conv_mixer_bias:
        params["conv"]["bias"] = jax.random.uniform(
            k_cb, (h,), jnp.float32, -bound, bound).astype(dtype)
    return params


def _held(x: jax.Array, dtype) -> jax.Array:
    """``x`` as ``dtype`` holds it, in ``dtype``: a rounding no fusion
    drops (an ``astype`` there and back is dropped under excess
    precision)."""
    if jnp.dtype(dtype) == jnp.float32:
        return x.astype(dtype)
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(
        x, exponent_bits=info.nexp, mantissa_bits=info.nmant).astype(dtype)


def short_conv_mixer(h: jax.Array, params, cfg: TransformerConfig, *,
                     kv_cache=None):
    """``h`` [b, n, hidden] (the layer's normed input) -> the mixer's
    output [b, n, hidden]; with ``kv_cache`` (a ``PagedKVCache`` of the
    ``STATE`` group: ``ops/paged_kv.py``) also the cache as the call
    leaves it.  No cache is a chunk from zeros in which every token is
    real."""
    from megatron_llm_tpu.ops.paged_kv import PagedKVCache

    if kv_cache is not None and not isinstance(kv_cache, PagedKVCache):
        raise NotImplementedError(
            "gated short-convolution layers ('conv') run through the "
            "serving engine's state group or the plain forward, not the "
            "legacy decode caches")
    b, n, hidden = h.shape
    cd = cfg.compute_jnp_dtype
    K = cfg.conv_taps

    with jax.named_scope("conv_in_proj"):
        bcx = h.astype(cd) @ params["in_proj"]["kernel"].astype(cd)
    with jax.named_scope("short_conv"):
        Bg, Cg, X = (bcx[..., i * hidden:(i + 1) * hidden] for i in range(3))
        z = _held(Bg * X, cd)
        if kv_cache is not None:
            state, = kv_cache.read_state()
            valid = kv_cache.valid_lens
        else:
            state = jnp.zeros((b, K - 1, hidden), cd)
            valid = jnp.full((b,), n, jnp.int32)
        w = params["conv"]["kernel"].astype(jnp.float32)        # [hidden, K]
        ext = jnp.concatenate([state.astype(cd), z], axis=1)
        acc = sum(ext[:, j:j + n].astype(jnp.float32) * w[:, j]
                  for j in range(K))
        if cfg.conv_mixer_bias:
            acc = acc + params["conv"]["bias"].astype(jnp.float32)
        y = Cg * _held(acc, cd)
        if kv_cache is not None:
            # the columns at the row's last valid tokens: ext[valid :
            # valid + K - 1] (an idle row keeps its own)
            new = jax.vmap(lambda e, v: jax.lax.dynamic_slice_in_dim(
                e, v, K - 1, axis=0))(ext, valid)
            kv_cache = kv_cache.write_state(new)
    with jax.named_scope("conv_out_proj"):
        out = y @ params["out_proj"]["kernel"].astype(cd)
    if kv_cache is not None:
        return out, kv_cache
    return out
