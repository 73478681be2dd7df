"""Transformer core: attention, MLP, layer, stack.

Reference: ``megatron/model/transformer.py`` —
``ParallelMLP`` (:77-141), ``CoreAttention`` (:144-277), ``ParallelAttention``
(:280-560), ``ParallelTransformerLayer`` (:612-846), ``ParallelTransformer``
(:927-1282).

TPU re-design highlights:

* batch-major ``[b, s, ...]`` layout (the reference is ``[s, b, ...]``);
  trailing dims stay aligned to the (sublane, lane) = (8/16, 128) tiling.
* the layer stack is a ``lax.scan`` over layer-stacked params — one trace,
  one compiled layer body, constant compile time in depth (the reference
  re-traces a Python loop of modules).
* activation recomputation is ``jax.checkpoint`` with policies standing in
  for the reference's 'uniform' / 'block' / 'selective' modes
  (transformer.py:1110-1176).
* the packed QKV projection keeps Megatron's grouped GQA layout
  ``[ng, q_per_group + 2, d]`` (transformer.py:334-365, 458-465) so weight
  conversion round-trips with the reference/HF are mechanical.
* attention math avoids materialising broadcast K/V for GQA: Q is reshaped
  to ``[b, ng, q_per_group, s, d]`` and contracted against group-shared K/V.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import (
    TRAINING,
    PositionEmbeddingType,
    TransformerConfig,
    refusal,
)
from megatron_llm_tpu.ops.activations import apply_mlp_activation
from megatron_llm_tpu.models.moe import moe_mlp, moe_mlp_dropless
from megatron_llm_tpu.ops.layernorm import (apply_norm, init_norm_params,
                                             layer_norm, rms_norm)
from megatron_llm_tpu.ops.paged_kv import PagedKVCache
from megatron_llm_tpu.ops.rope import (apply_rotary_at, apply_rotary_emb,
                                        precompute_freqs_cis)
from megatron_llm_tpu.ops.softmax import (
    causal_mask,
    fused_scale_mask_softmax,
    sliding_window_mask,
)
from megatron_llm_tpu.parallel.layers import (
    column_parallel_linear,
    init_linear_params,
    init_method_for,
    init_method_normal,
    row_parallel_linear,
    scaled_init_method_normal,
)
from megatron_llm_tpu.parallel.sharding import constrain


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _qkv_out_dim(cfg: TransformerConfig) -> int:
    """The fused projection's width: a key-value group's query heads, its
    key head, its value head and, under ``cfg.attention_output_gate``,
    its query heads' gates."""
    ng = cfg.num_query_groups
    qpg = cfg.num_attention_heads // ng
    gates = qpg if cfg.attention_output_gate else 0
    return ng * (qpg + 2 + gates) * cfg.head_dim


def init_latent_attention_params(key, cfg: TransformerConfig, dtype):
    """Latent attention's projections (``cfg.kv_lora_rank``), all
    bias-free: ``query`` h -> heads x (nope + rope); ``kv_down`` h ->
    latent + ONE rotary key; ``kv_norm`` the latent's RMSNorm scale;
    ``kv_up`` latent -> heads x (nope keys + values), a head's keys
    before its values; ``dense`` heads x values -> h.  With a compressed
    query (``cfg.q_lora_rank``) the query's projection is two,
    ``query_down`` h -> q_lora_rank and ``query`` q_lora_rank -> heads x
    (nope + rope), ``query_norm`` the RMSNorm scale between them; with a
    sparse-attention indexer, its leaves under ``indexer``."""
    kq, kd, ku, ko = jax.random.split(key, 4)
    init = init_method_for(cfg)
    out_init = (
        scaled_init_method_normal(cfg.init_method_std, cfg.num_layers)
        if cfg.use_scaled_init_method
        else init
    )
    nh, r = cfg.num_attention_heads, cfg.kv_lora_rank

    def linear(k, n_in, n_out, method=init):
        return init_linear_params(k, n_in, n_out, bias=False,
                                  init_method=method, dtype=dtype)

    params = {
        "query": linear(kq, cfg.q_lora_rank or cfg.hidden_size,
                        nh * cfg.qk_head_dim),
        "kv_down": linear(kd, cfg.hidden_size, r + cfg.qk_rope_head_dim),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "kv_up": linear(ku, r, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "dense": linear(ko, nh * cfg.v_head_dim, cfg.hidden_size, out_init),
    }
    if cfg.q_lora_rank is not None:
        params["query_down"] = linear(jax.random.fold_in(kq, 1),
                                      cfg.hidden_size, cfg.q_lora_rank)
        params["query_norm"] = {"scale": jnp.ones((cfg.q_lora_rank,), dtype)}
    if cfg.dsa_index_heads > 0:
        params["indexer"] = init_indexer_params(
            jax.random.split(jax.random.fold_in(kd, 1), 3), cfg, dtype, init)
    return params


def init_indexer_params(keys, cfg: TransformerConfig, dtype, init):
    """The sparse-attention indexer's three bias-free projections (its
    heads' queries, its one key head, a weight a head) and the key's
    LayerNorm.  The key and the weights read the layer's normed input;
    the queries read it too, or the compressed query
    (``cfg.dsa_index_query``)."""
    hi, di = cfg.dsa_index_heads, cfg.dsa_index_head_dim
    q_in = (cfg.q_lora_rank if cfg.dsa_index_query == "compressed"
            else cfg.hidden_size)
    return {
        "query": init_linear_params(keys[0], q_in, hi * di, bias=False,
                                    init_method=init, dtype=dtype),
        "key": init_linear_params(keys[1], cfg.hidden_size, di, bias=False,
                                  init_method=init, dtype=dtype),
        "weights": init_linear_params(keys[2], cfg.hidden_size, hi,
                                      bias=False, init_method=init,
                                      dtype=dtype),
        "key_norm": {"scale": jnp.ones((di,), dtype),
                     "bias": jnp.zeros((di,), dtype)},
    }


def init_attention_params(key, cfg: TransformerConfig, dtype):
    if cfg.latent_attention:
        return init_latent_attention_params(key, cfg, dtype)
    k1, k2 = jax.random.split(key)
    init = init_method_for(cfg)
    out_init = (
        scaled_init_method_normal(cfg.init_method_std, cfg.num_layers)
        if cfg.use_scaled_init_method
        else init
    )
    params = {
        # packed grouped-QKV column-parallel projection
        # (reference: transformer.py:334-365); add_qkv_bias gives the
        # in-projection a bias even in an otherwise bias-free model
        # (Qwen2)
        "query_key_value": init_linear_params(
            k1, cfg.hidden_size, _qkv_out_dim(cfg),
            bias=cfg.add_bias_linear or cfg.add_qkv_bias,
            init_method=init, dtype=dtype,
        ),
        # row-parallel output projection (reference: transformer.py:372-380)
        "dense": init_linear_params(
            k2, cfg.num_attention_heads * cfg.head_dim, cfg.hidden_size,
            bias=cfg.add_bias_linear, init_method=out_init, dtype=dtype,
        ),
    }
    if cfg.qk_norm:
        # one learned scale over the WHOLE query / key projection
        params["q_norm"] = {"scale": jnp.ones(
            (cfg.num_attention_heads * cfg.head_dim,), dtype)}
        params["k_norm"] = {"scale": jnp.ones(
            (cfg.num_query_groups * cfg.head_dim,), dtype)}
    elif cfg.qk_norm_per_head:
        # one learned scale of a head's width, shared by the heads
        params["q_norm"] = {"scale": jnp.ones((cfg.head_dim,), dtype)}
        params["k_norm"] = {"scale": jnp.ones((cfg.head_dim,), dtype)}
    if cfg.dsa_index_heads > 0:
        params["indexer"] = init_indexer_params(
            jax.random.split(k1, 4)[1:], cfg, dtype, init)
    return params


def init_cross_attention_params(key, cfg: TransformerConfig, dtype):
    """Decoder cross-attention projections (reference ``ParallelAttention``
    with ``AttnType.cross_attn``, transformer.py:344-365): separate
    column-parallel Q (from decoder states) and packed KV (from encoder
    output), row-parallel dense.  Cross-attention always uses the full head
    count (no GQA)."""
    k1, k2, k3 = jax.random.split(key, 3)
    init = init_method_normal(cfg.init_method_std)
    out_init = (
        scaled_init_method_normal(cfg.init_method_std, cfg.num_layers)
        if cfg.use_scaled_init_method
        else init
    )
    nh_d = cfg.num_attention_heads * cfg.head_dim
    return {
        "query": init_linear_params(
            k1, cfg.hidden_size, nh_d,
            bias=cfg.add_bias_linear, init_method=init, dtype=dtype,
        ),
        "key_value": init_linear_params(
            k2, cfg.hidden_size, 2 * nh_d,
            bias=cfg.add_bias_linear, init_method=init, dtype=dtype,
        ),
        "dense": init_linear_params(
            k3, nh_d, cfg.hidden_size,
            bias=cfg.add_bias_linear, init_method=out_init, dtype=dtype,
        ),
    }


def init_mlp_params(key, cfg: TransformerConfig, dtype):
    k1, k2 = jax.random.split(key)
    init = init_method_for(cfg)
    out_init = (
        scaled_init_method_normal(cfg.init_method_std, cfg.num_layers)
        if cfg.use_scaled_init_method
        else init
    )
    # GLU doubles the first projection (reference: transformer.py:92-102)
    mult = 2 if cfg.glu_activation else 1
    return {
        "dense_h_to_4h": init_linear_params(
            k1, cfg.hidden_size, mult * cfg.ffn_hidden_size,
            bias=cfg.add_bias_linear, init_method=init, dtype=dtype,
        ),
        "dense_4h_to_h": init_linear_params(
            k2, cfg.ffn_hidden_size, cfg.hidden_size,
            bias=cfg.add_bias_linear, init_method=out_init, dtype=dtype,
        ),
    }


def init_layer_params(key, cfg: TransformerConfig, dtype,
                      layer_type: str = "encoder", sparse: bool = True):
    """``sparse`` False: a sparse model's leading dense layer, whose MLP
    is the dense one of ``ffn_hidden_size``.  For a stack whose mixers
    are of several kinds (state-space or gated short-convolution layers
    beside attention) the mixer is left out: the kinds have other leaves
    and are stacked apart (``init_stack_params``).  A layer of ONE
    sublayer (``cfg.one_sublayer``) holds its one norm here and nothing
    else: its mixer or its experts are stacked apart by kind."""
    ka, km, kn = jax.random.split(key, 3)
    if cfg.one_sublayer:
        return {"input_norm": init_norm_params(
            cfg.hidden_size, cfg.normalization, dtype)}
    if cfg.num_experts > 1 and sparse:
        from megatron_llm_tpu.models.moe import init_moe_mlp_params

        mlp_params = init_moe_mlp_params(km, cfg, dtype)
    else:
        mlp_params = init_mlp_params(km, cfg, dtype)
    params = {
        "input_norm": init_norm_params(cfg.hidden_size, cfg.normalization, dtype),
        "mlp": mlp_params,
    }
    if not cfg.mixers_by_kind:
        params["attention"] = init_attention_params(ka, cfg, dtype)
    if not cfg.parallel_attn:
        # pre-MLP norm (reference: post_attention_layernorm)
        params["post_attention_norm"] = init_norm_params(
            cfg.hidden_size, cfg.normalization, dtype
        )
    if cfg.sublayer_output_norm:
        # the norms of each sublayer's OUTPUT, x + norm(f(norm(x))): HF
        # afmoe calls the first ``post_attention_layernorm`` and the norm
        # before the MLP ``pre_mlp_layernorm``; here ``post_attention_norm``
        # is the norm before the MLP, as everywhere in this tree
        for name in ("attention_output_norm", "mlp_output_norm"):
            params[name] = init_norm_params(
                cfg.hidden_size, cfg.normalization, dtype)
    if cfg.parallel_layernorm:
        # Falcon-40B separate LN for the MLP branch (transformer.py:804-845)
        params["mlp_norm"] = init_norm_params(
            cfg.hidden_size, cfg.normalization, dtype
        )
    if layer_type == "decoder":
        # T5 decoder: cross-attention over encoder output + its own norm
        # (reference: LayerType.decoder, transformer.py:695-714)
        params["inter_attention"] = init_cross_attention_params(kn, cfg, dtype)
        params["post_inter_attention_norm"] = init_norm_params(
            cfg.hidden_size, cfg.normalization, dtype
        )
    return params


def init_stack_params(key, cfg: TransformerConfig, dtype, layer_type: str = "encoder"):
    """Layer-stacked params: every leaf gets a leading [num_layers] axis
    (scanned).  Reference builds a Python list of modules
    (transformer.py:983-1014).  A sparse model's leading dense layers
    (``cfg.moe_first_dense_layers``) have other leaves, so they are
    stacked apart, under ``dense_layers``, and ``layers`` holds the
    sparse ones only.  So are the mixers of a stack with state-space or
    gated short-convolution layers (``cfg.mixers_by_kind``):
    ``layers['mamba']`` (``layers['conv']``) and ``layers['attention']``
    hold the layers of each kind in their order over the WHOLE depth,
    under the norms and the MLP that every layer has: a leading dense
    layer's mixer is a member of its kind's stack like any other, and
    only its norms and its MLP live under ``dense_layers``; in a stack of one
    sublayer a layer (``cfg.one_sublayer``) so is the third kind,
    ``layers['moe']`` (what ``layers['mlp']`` holds elsewhere, over the
    expert layers alone), under the ONE norm every layer has."""
    keys = jax.random.split(key, cfg.num_layers)
    D = cfg.moe_first_dense_layers

    def stack(ks, sparse):
        layers = [init_layer_params(k, cfg, dtype, layer_type, sparse)
                  for k in ks]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)

    params = {
        "layers": stack(keys[D:], True),
        "final_norm": init_norm_params(cfg.hidden_size, cfg.normalization, dtype),
    }
    if D:
        params["dense_layers"] = stack(keys[:D], False)
    if cfg.loop_steps > 1:
        # a looped stack's exit gate, a linear layer with a bias over the
        # stream each pass's final norm leaves (hidden_size + 1
        # parameters); its key is folded from the stack's, so the layers
        # draw what they would without it
        params["exit_gate"] = {
            "kernel": (cfg.init_method_std * jax.random.normal(
                jax.random.fold_in(key, cfg.num_layers),
                (cfg.hidden_size,), jnp.float32)).astype(dtype),
            "bias": jnp.zeros((), dtype)}
    for kind in cfg.mixer_counts:
        init, at = init_attention_params, 0
        if kind == "mamba":
            from megatron_llm_tpu.models.mamba import init_mamba_params as init
        elif kind == "conv":
            from megatron_llm_tpu.models.short_conv import (
                init_short_conv_params as init)
        elif kind == "retention":
            from megatron_llm_tpu.models.retention import (
                init_retention_params as init)
        elif kind == "gated_delta":
            from megatron_llm_tpu.models.gated_delta import (
                init_gated_delta_params as init)
        elif kind == "moe":
            from megatron_llm_tpu.models.moe import (
                init_moe_mlp_params as init)
            at = 1
        # the key the layer's attention (its MLP) would have had
        mixers = [init(jax.random.split(k, 3)[at], cfg, dtype)
                  for i, k in enumerate(keys)
                  if cfg.mixer_index(i)[0] == kind]
        params["layers"][kind] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *mixers)
    return params


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _split_qkv(mixed: jax.Array, cfg: TransformerConfig):
    """mixed: [b, s, ng*(qpg+2)*d] in Megatron grouped layout ->
    q [b, s, nh, d], k [b, s, ng, d], v [b, s, ng, d]
    (reference: transformer.py:458-465), and the output gate's
    pre-activations [b, s, nh, d] (None without
    ``cfg.attention_output_gate``): a group's last qpg heads of its
    2 qpg + 2, so the gate rides the ONE read of the normed input that
    the projection makes, and a group's columns stay together under the
    'heads' axis."""
    b, s, _ = mixed.shape
    ng = cfg.num_query_groups
    qpg = cfg.num_attention_heads // ng
    d = cfg.head_dim
    mixed = mixed.reshape(b, s, ng, -1, d)
    q = mixed[:, :, :, :qpg, :].reshape(b, s, ng * qpg, d)
    k = mixed[:, :, :, qpg, :]
    v = mixed[:, :, :, qpg + 1, :]
    gate = (mixed[:, :, :, qpg + 2:, :].reshape(b, s, ng * qpg, d)
            if cfg.attention_output_gate else None)
    return q, k, v, gate


def _projection_rms_norm(x: jax.Array, scale: jax.Array, eps: float):
    """x [b, s, heads, d]: RMSNorm over the whole projection (all heads
    together, the mean square over heads * d), then the learned scale of
    that width; in fp32, back to x's dtype."""
    b, s, n, d = x.shape
    flat = x.reshape(b, s, n * d).astype(jnp.float32)
    flat = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    return (flat * scale.astype(jnp.float32)).astype(x.dtype).reshape(
        b, s, n, d)


def indexer_projections(x: jax.Array, params, cfg: TransformerConfig,
                        positions: jax.Array,
                        query_input: Optional[jax.Array] = None):
    """The sparse-attention indexer's query heads ``[b, s, Hi, di]`` and
    one key head ``[b, s, di]`` (both rotated at ``positions`` [b, s] in
    their first ``cfg.dsa_index_rope_dim`` dimensions, all of them where
    that is None; compute dtype) and its head weights ``[b, s, Hi]``
    (fp32, with the two scale factors ``Hi^-1/2`` and ``di^-1/2`` folded
    in) from the layer's normed input ``x`` [b, s, h], the queries from
    ``query_input`` where one is given (the compressed query); and, with
    them, the top-k: what ``PagedKVCache.attend`` and ``attend_latent``
    take as ``index``."""
    b, s, _ = x.shape
    hi, di = cfg.dsa_index_heads, cfg.dsa_index_head_dim
    cd = cfg.compute_jnp_dtype
    xc = x.astype(cd)
    xq = xc if query_input is None else query_input.astype(cd)
    iq = (xq @ params["query"]["kernel"].astype(cd)).reshape(b, s, hi, di)
    ik = layer_norm(xc @ params["key"]["kernel"].astype(cd),
                    params["key_norm"]["scale"], params["key_norm"]["bias"],
                    eps=cfg.layernorm_epsilon)
    iw = (xc @ params["weights"]["kernel"].astype(cd)).astype(jnp.float32)
    iw = iw * (hi ** -0.5) * (di ** -0.5)
    iq = apply_rotary_at(iq, positions, cfg.rope_theta, cfg.rope_sections,
                         rot_d=cfg.dsa_index_rope_dim)
    ik = apply_rotary_at(ik[:, :, None, :], positions, cfg.rope_theta,
                         cfg.rope_sections,
                         rot_d=cfg.dsa_index_rope_dim)[:, :, 0, :]
    return iq, ik, iw, cfg.dsa_topk


def core_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg: TransformerConfig,
    attention_mask: Optional[jax.Array],
    dropout_key: Optional[jax.Array],
    train: bool,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Unfused attention (reference ``CoreAttention``, transformer.py:144-277):
    scaled QK^T -> scale-mask-softmax -> dropout -> PV.  GQA contracts
    group-shared K/V without materialising the head broadcast
    (the reference broadcasts K/V to all Q heads, :458-465).  With no
    ``attention_mask`` the mask is causal, within ``window`` keys if
    given.  ``scale`` multiplies the scores (None: ``1 / sqrt(d)``)."""
    b, sq, nh, d = q.shape
    ng = k.shape[2]
    qpg = nh // ng
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    qg = q.reshape(b, sq, ng, qpg, d)
    # scores: [b, ng, qpg, sq, sk]
    scores = jnp.einsum("bsgpd,btgd->bgpst", qg, k)

    if attention_mask is None:
        if window is not None:
            mask = sliding_window_mask(sq, sk, window)
        else:
            mask = causal_mask(sq, sk)
        mask = mask[None, None, None]  # [1,1,1,sq,sk]
    else:
        # [b, 1, sq, sk] -> [b, 1, 1, sq, sk]
        mask = attention_mask[:, :, None]

    probs = fused_scale_mask_softmax(
        scores, mask, scale=scale, softmax_in_fp32=cfg.attention_softmax_in_fp32
    )

    if train and cfg.attention_dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - cfg.attention_dropout, probs.shape)
        probs = probs * keep.astype(probs.dtype) / (1.0 - cfg.attention_dropout)

    ctx = jnp.einsum("bgpst,btgd->bsgpd", probs, v)
    return ctx.reshape(b, sq, nh, d)


def latent_attention(
    x: jax.Array,
    params,
    cfg: TransformerConfig,
    *,
    attention_mask: Optional[jax.Array],
    position_ids: Optional[jax.Array],
    dropout_key: Optional[jax.Array],
    train: bool,
    sequence_parallel: bool = False,
    kv_cache=None,
):
    """Latent attention (``cfg.kv_lora_rank``; DeepSeek's MLA), ONE
    function in two forms.

    A token's keys and values of every head come from one latent ``c``
    (``kv_down``'s first ``kv_lora_rank`` outputs, RMSNorm'd) through
    ``kv_up``, and every head shares one rotary key ``k_rope``
    (``kv_down``'s last ``qk_rope_head_dim``); a query head is
    ``[q_nope ; q_rope]`` and only the rope parts rotate.  Scores
    ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``.

    * **Expanded** (no cache: ``GPTModel``'s plain forward, training):
      ``kv_up`` is applied to every token's latent (scope
      ``mla_expand``) and heads of ``nope + rope`` attend values of
      ``v_head_dim`` through XLA (``core_attention``, or the q-chunked
      form at long sequences; the values ride at the keys' width).
      A ``PagedKVCache``'s CHUNK on the kernel path is expanded too,
      but inside the kernel (``mla_attention_prefill``: each block of the
      pool's latents meets each head's slice of ``kv_up`` in VMEM and
      the head's ``[q_nope ; q_rope]`` attends keys of ``nope + rope``
      and values of ``v_head_dim`` that never reach HBM; half the
      absorbed chunk's operations at a chunk of 512, fewer from about
      170 live rows on).  ``PagedKVCache.expands_latents`` decides, by
      the query length and the path.
    * **Absorbed** (a ``PagedKVCache``'s decode step,
      ``mla_attention_decode``, and the dense fallback, a chunk's too):
      ``kv_up``'s key half is folded into the query and its value half
      is applied to the attention's output (scope ``mla_absorb``), so
      the cache is read as it lies: all heads attend ONE key ``[c ;
      k_rope]`` a token whose first ``kv_lora_rank`` values are also its
      value (``PagedKVCache.attend_latent``); bytes bound a step, and
      expanding would read no fewer.

    A COMPRESSED QUERY (``cfg.q_lora_rank``): the queries come from the
    normed input through two projections with an RMSNorm of its own
    between them (scopes ``mla_query_down``, which holds the norm, and
    ``mla_query_up``).

    THE SELECTION OVER LATENTS (``cfg.dsa_index_heads``; GLM-5's
    ``glm_moe_dsa``): an indexer (``indexer_projections``, its queries
    from the compressed query where ``cfg.dsa_index_query`` says so)
    chooses each query's ``dsa_topk`` positions and the query attends
    those latents only, in whichever form: the cache writes the
    indexer's key beside the latent row and reads under the choice
    (``PagedKVCache.attend_latent(index=)``), and the cache-less forward
    attends the expanded heads under ``ops/dsa.py``'s mask.

    The legacy decode caches (contiguous, rolling, int8) are refused."""
    b, s, _ = x.shape
    cd = cfg.compute_jnp_dtype
    nh, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if kv_cache is not None and not isinstance(kv_cache, PagedKVCache):
        raise NotImplementedError(
            "latent attention (kv_lora_rank) runs through the paged cache "
            "or the plain forward, not the legacy decode caches")
    c_q = None
    if cfg.q_lora_rank is not None:
        with jax.named_scope("mla_query_down"):
            c_q = rms_norm(
                column_parallel_linear(
                    x, params["query_down"], out_logical=None,
                    sequence_parallel=sequence_parallel, compute_dtype=cd),
                params["query_norm"]["scale"], eps=cfg.layernorm_epsilon)
        with jax.named_scope("mla_query_up"):
            q = column_parallel_linear(
                c_q, params["query"], out_logical="heads",
                compute_dtype=cd).reshape(b, s, nh, dn + dr)
    else:
        q = column_parallel_linear(
            x, params["query"], out_logical="heads",
            sequence_parallel=sequence_parallel, compute_dtype=cd,
        ).reshape(b, s, nh, dn + dr)
    kv = column_parallel_linear(
        x, params["kv_down"], out_logical=None,
        sequence_parallel=sequence_parallel, compute_dtype=cd)
    c = rms_norm(kv[..., :r], params["kv_norm"]["scale"],
                 eps=cfg.layernorm_epsilon)
    positions = position_ids
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q_nope = q[..., :dn]
    q_rope = apply_rotary_at(q[..., dn:], positions, cfg.rope_theta)
    k_rope = apply_rotary_at(kv[..., None, r:], positions, cfg.rope_theta)
    w_up = params["kv_up"]["kernel"].astype(cd).reshape(r, nh, dn + dv)
    index = None
    if cfg.dsa_index_heads > 0:
        with jax.named_scope("dsa_indexer"):
            index = indexer_projections(
                x, params["indexer"], cfg, positions,
                query_input=(c_q if cfg.dsa_index_query == "compressed"
                             else None))

    new_cache = None
    if kv_cache is not None:
        scale = 1.0 / math.sqrt(dn + dr)
        if kv_cache.expands_latents(s):
            ctx, new_cache = kv_cache.attend_latent(
                q_nope, q_rope, c, k_rope[:, :, 0], scale, kv_up=w_up,
                index=index)
        else:
            with jax.named_scope("mla_absorb"):
                q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope, w_up[..., :dn])
            ctx, new_cache = kv_cache.attend_latent(
                q_lat, q_rope, c, k_rope[:, :, 0], scale, index=index)
            with jax.named_scope("mla_absorb"):
                ctx = jnp.einsum("bsnr,rnd->bsnd", ctx, w_up[..., dn:])
    else:
        with jax.named_scope("mla_expand"):
            up = jnp.einsum("bsr,rnd->bsnd", c, w_up)
        k = jnp.concatenate(
            [up[..., :dn], jnp.broadcast_to(k_rope, (b, s, nh, dr))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        # the values at the keys' width (zeros appended), so that the
        # attention the repo has takes them; its scale is the queries'
        v = jnp.pad(up[..., dn:], [(0, 0)] * 3 + [(0, dn + dr - dv)])
        from megatron_llm_tpu.ops.chunked_attention import (
            CHUNKED_ATTENTION_MIN_SEQ,
            chunked_causal_attention,
        )

        if index is not None:
            # the cache-less forward selects too: what tier-1 holds the
            # paged programs against
            if attention_mask is not None:
                raise NotImplementedError(
                    "sparse attention (dsa_index_heads > 0) runs through "
                    "the paged cache or the plain causal forward, not an "
                    "explicit attention mask")
            from megatron_llm_tpu.ops.dsa import causal_selected_attention

            ctx = causal_selected_attention(q, k, v, *index)
        elif (attention_mask is None and s >= CHUNKED_ATTENTION_MIN_SEQ
                and not (train and cfg.attention_dropout > 0.0)):
            ctx = chunked_causal_attention(q, k, v, causal=True)
        else:
            ctx = core_attention(q, k, v, cfg, attention_mask, dropout_key,
                                 train)
        ctx = ctx[..., :dv]

    out = row_parallel_linear(
        ctx.reshape(b, s, nh * dv), params["dense"], in_logical="heads",
        sequence_parallel=sequence_parallel, compute_dtype=cd)
    if kv_cache is not None:
        return out, new_cache
    return out


def qkv_heads(x: jax.Array, params, cfg: TransformerConfig, *,
              freqs: Optional[tuple], position_ids: Optional[jax.Array],
              sequence_parallel: bool = False,
              layer_type: Optional[str] = None):
    """A layer's queries, keys and values as its mixer takes them: the
    packed projection split by head (``q`` [b, s, heads, d], ``k`` and
    ``v`` [b, s, groups, d], the output gate or None), the QK-norm the
    config has, the rotary variant of ``layer_type``.  What ``attention``
    and a power-retention layer (``models/retention.py``) share.  Also
    the positions the rotation took as given (None where it read a table
    or nothing rotates): the sparse-attention indexer rotates by them."""
    _, yarn = cfg.attention_of(layer_type)
    mixed = column_parallel_linear(
        x, params["query_key_value"],
        out_logical="heads",
        sequence_parallel=sequence_parallel,
        compute_dtype=cfg.compute_jnp_dtype,
    )
    q, k, v, gate = _split_qkv(mixed, cfg)

    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q = _projection_rms_norm(q, params["q_norm"]["scale"],
                                     cfg.layernorm_epsilon)
            k = _projection_rms_norm(k, params["k_norm"]["scale"],
                                     cfg.layernorm_epsilon)

    elif cfg.qk_norm_per_head:
        with jax.named_scope("qk_norm"):
            # each head by itself: the norm over the last axis
            q = rms_norm(q, params["q_norm"]["scale"],
                         eps=cfg.layernorm_epsilon)
            k = rms_norm(k, params["k_norm"]["scale"],
                         eps=cfg.layernorm_epsilon)

    positions = None
    if (cfg.position_embedding_type == PositionEmbeddingType.none
            or not cfg.rotates(layer_type)):
        # nothing rotates and nothing is added, on any path: the model's
        # every layer, or the layers of this type (cfg.rope_layer_types)
        pass
    elif (cfg.rope_sections is not None or cfg.dsa_index_heads > 0
            or cfg.layer_types is not None):
        # positions are taken as given, with no table: [b, s], or
        # [streams, b, s] for the sectioned embedding (a text token's
        # streams coincide and this is the plain embedding); a layer
        # type's own variant (YaRN or none) with no table a type
        positions = position_ids
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(q.shape[1])[None],
                                         q.shape[:2])
        # a head's first rot_d dimensions rotate (rotary_percent, as
        # ``rotary_freqs`` reads it for a model of one type): all of them
        # at 1.0
        rot_d = int(cfg.head_dim * cfg.rotary_percent)
        rot_d -= rot_d % 2
        q = apply_rotary_at(q, positions, cfg.rope_theta, cfg.rope_sections,
                            yarn, rot_d)
        k = apply_rotary_at(k, positions, cfg.rope_theta, cfg.rope_sections,
                            yarn, rot_d)
    elif cfg.position_embedding_type == PositionEmbeddingType.rotary and freqs is not None:
        cos, sin = freqs
        q = apply_rotary_emb(q, cos, sin, position_ids)
        k = apply_rotary_emb(k, cos, sin, position_ids)
    return q, k, v, gate, positions


def attention(
    x: jax.Array,
    params,
    cfg: TransformerConfig,
    *,
    freqs: Optional[tuple],
    attention_mask: Optional[jax.Array],
    position_ids: Optional[jax.Array],
    dropout_key: Optional[jax.Array],
    train: bool,
    sequence_parallel: bool = False,
    kv_cache=None,
    layer_type: Optional[str] = None,
) -> jax.Array:
    """Full attention block (reference ``ParallelAttention``,
    transformer.py:280-560): column-parallel QKV, RoPE, core/flash attention,
    row-parallel dense.  ``kv_cache`` enables incremental decoding
    (reference inference path :412-505): a ``PagedKVCache`` (the serving
    engine) or one of the legacy decode stack's dicts with 'k','v','index'
    (``text_generation/generation.py::init_kv_caches``).  ``layer_type``:
    this layer's, of a model with ``cfg.layer_types``: it decides the
    window and the rotary variant (``cfg.attention_of``)."""
    if cfg.latent_attention:
        return latent_attention(
            x, params, cfg, attention_mask=attention_mask,
            position_ids=position_ids, dropout_key=dropout_key, train=train,
            sequence_parallel=sequence_parallel, kv_cache=kv_cache)
    window, _ = cfg.attention_of(layer_type)
    # what the scores are multiplied by: a family's own multiplier, else
    # 1 / sqrt(head_dim)
    scale = (1.0 / math.sqrt(cfg.head_dim)
             if cfg.attention_multiplier is None
             else float(cfg.attention_multiplier))
    q, k, v, gate, positions = qkv_heads(
        x, params, cfg, freqs=freqs, position_ids=position_ids,
        sequence_parallel=sequence_parallel, layer_type=layer_type)
    index = None
    if cfg.dsa_index_heads > 0 and positions is not None:
        with jax.named_scope("dsa_indexer"):
            index = indexer_projections(x, params["indexer"], cfg,
                                        positions)

    new_cache = None
    paged_ctx = None
    if isinstance(kv_cache, PagedKVCache):
        # the serving engine's paged cache (ops/paged_kv.py owns it):
        # scatter this call's K/V (and the indexer's keys) into the
        # pool, attend through the path the cache carries
        paged_ctx, new_cache = kv_cache.attend(q, k, v, window, index=index,
                                               scale=scale)
    elif index is not None:
        # the cache-less forward selects too: what tier-1 holds the
        # paged programs against
        if kv_cache is not None or attention_mask is not None:
            raise NotImplementedError(
                "sparse attention (dsa_index_heads > 0) runs through the "
                "paged cache or the plain causal forward, not the legacy "
                "decode caches nor an explicit attention mask")
        from megatron_llm_tpu.ops.dsa import causal_selected_attention

        paged_ctx = causal_selected_attention(q, k, v, *index)
    elif kv_cache is not None and "rolling" in kv_cache:
        # ROLLING cache (sliding-window models): a ring buffer of exactly
        # window slots — decode memory O(window), not O(total).  Slot
        # j holds the newest position == j (mod W) written so far; the
        # mask recovers each slot's position and applies the same
        # causal+window validity as the linear cache.  Beyond-reference:
        # the reference's inference cache is always [b, total]
        # (transformer.py:433-505).  Constraint (documented in
        # init_kv_caches): any single forward writes <= W tokens.
        idx = kv_cache["index"]
        W = kv_cache["k"].shape[1]
        n = k.shape[1]
        # attend over [pre-chunk ring || current chunk]: the ring is only
        # read for positions < idx, so in-chunk writes can never clobber
        # keys the chunk's own queries still need (any chunk length works)
        slot = jnp.arange(W)
        last_pre = idx - 1
        # newest position == slot (mod W) written before this chunk;
        # negative = never written (all slots at idx == 0)
        cache_pos = last_pre - ((last_pre - slot) % W)
        pos = idx + jnp.arange(n)                # query positions
        key_pos = jnp.concatenate([cache_pos, pos])
        valid = (key_pos[None, :] >= 0) & (key_pos[None, :] <= pos[:, None])
        assert window is not None, \
            "rolling KV caches require a sliding window on every layer"
        valid &= key_pos[None, :] > pos[:, None] - window
        mask = ~valid[None, None]
        # write the chunk into the ring AFTER the read view is formed; for
        # chunks longer than the ring only the last W tokens survive —
        # writing all n would scatter duplicate slot indices (unspecified
        # winner) where only the newest must win
        if n >= W:
            w_pos, wk, wv = pos[-W:], k[:, -W:], v[:, -W:]
        else:
            w_pos, wk, wv = pos, k, v
        write = w_pos % W
        ck = kv_cache["k"].at[:, write].set(wk)
        cv = kv_cache["v"].at[:, write].set(wv)
        k = jnp.concatenate([kv_cache["k"], k], axis=1)
        v = jnp.concatenate([kv_cache["v"], v], axis=1)
        attention_mask = jnp.broadcast_to(mask,
                                          (x.shape[0],) + mask.shape[1:])
        new_cache = {"k": ck, "v": cv, "index": idx + q.shape[1],
                     "rolling": None}
    elif kv_cache is not None and "k_q" in kv_cache:
        # int8-quantized linear cache (beyond-reference): K/V stored as
        # int8 with per-(batch, position, group) fp32 absmax scales —
        # at long context the KV bytes dominate decode HBM traffic, and
        # this halves them vs bf16 (quarters vs fp32).  Quantize on
        # write (chunk-local scales), dequantize on read; the int8
        # arrays are what cross HBM each step.
        idx = kv_cache["index"]
        from megatron_llm_tpu.quantization import absmax_quantize_int8
        # [b, n, g, d] -> int8 + [b, n, g] per-position scales
        kq, ks = absmax_quantize_int8(k, axis=-1)
        vq, vs = absmax_quantize_int8(v, axis=-1)
        upd = jax.lax.dynamic_update_slice_in_dim
        ckq = upd(kv_cache["k_q"], kq, idx, axis=1)
        cks = upd(kv_cache["k_scale"], ks, idx, axis=1)
        cvq = upd(kv_cache["v_q"], vq, idx, axis=1)
        cvs = upd(kv_cache["v_scale"], vs, idx, axis=1)
        sk = ckq.shape[1]
        pos = idx + jnp.arange(k.shape[1])
        valid = jnp.arange(sk)[None, :] <= pos[:, None]  # [sq, sk]
        if window is not None:
            valid &= jnp.arange(sk)[None, :] > pos[:, None] - window
        mask = ~valid[None, None]  # [1,1,sq,sk]
        cdt = k.dtype
        k = ckq.astype(cdt) * cks[..., None].astype(cdt)
        v = cvq.astype(cdt) * cvs[..., None].astype(cdt)
        attention_mask = jnp.broadcast_to(mask, (x.shape[0],) + mask.shape[1:])
        new_cache = {"k_q": ckq, "k_scale": cks, "v_q": cvq,
                     "v_scale": cvs, "index": idx + q.shape[1]}
    elif kv_cache is not None:
        # incremental decode: write current k/v at cache index, attend over
        # the full cache (reference: transformer.py:433-505)
        idx = kv_cache["index"]
        ck = jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], k, idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], v, idx, axis=1)
        sk = ck.shape[1]
        pos = idx + jnp.arange(k.shape[1])
        valid = jnp.arange(sk)[None, :] <= pos[:, None]  # [sq, sk]
        if window is not None:
            valid &= jnp.arange(sk)[None, :] > pos[:, None] - window
        mask = ~valid[None, None]  # [1,1,sq,sk]
        k, v = ck, cv
        attention_mask = jnp.broadcast_to(mask, (x.shape[0],) + mask.shape[1:])
        new_cache = {"k": ck, "v": cv, "index": idx + q.shape[1]}

    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)

    from megatron_llm_tpu import topology as _topo

    cp_size = (
        _topo.get_context_parallel_world_size()
        if _topo.model_parallel_is_initialized() else 1
    )
    # flash/ring/chunked all hardcode causal(+window) masking and no
    # dropout — one eligibility predicate for the three paths
    flash_eligible = (
        kv_cache is None
        and attention_mask is None
        and not (train and cfg.attention_dropout > 0.0)
    )
    use_ring = cp_size > 1 and flash_eligible
    use_flash = cfg.use_flash_attn and flash_eligible
    if paged_ctx is not None:
        ctx = paged_ctx
    elif use_ring:
        from megatron_llm_tpu.parallel.ring_attention import (
            context_parallel_attention,
        )
        from megatron_llm_tpu.parallel.ulysses import (
            ulysses_context_attention,
            ulysses_supported,
        )

        # three context-parallel algorithms (all absent from the
        # reference): 'ulysses' all-to-alls heads<->sequence so attention
        # runs dense and local (needs heads % cp == 0); 'zigzag' is the
        # load-balanced causal ring (half-chunk pair layout, fully-masked
        # sub-blocks skipped); 'ring' permutes K/V around the cp ring
        # (any head count).  Ulysses falls back to ring when the head
        # counts don't divide cp; zigzag falls back when the local
        # sequence is odd.
        algo = getattr(cfg, "context_parallel_algo", "ring")
        if algo == "ulysses" and ulysses_supported(
                cfg.num_attention_heads, cfg.num_query_groups, cp_size):
            ctx = ulysses_context_attention(
                q, k, v,
                causal=True,
                sliding_window=window,
                softmax_scale=scale,
            )
        elif algo == "zigzag" and (q.shape[1] // cp_size) % 2 == 0:
            from megatron_llm_tpu.parallel.zigzag_ring import (
                zigzag_context_attention,
            )

            ctx = zigzag_context_attention(
                q, k, v,
                causal=True,
                sliding_window=window,
                softmax_scale=scale,
            )
        else:
            ctx = context_parallel_attention(
                q, k, v,
                causal=True,
                sliding_window=window,
                softmax_scale=scale,
            )
    elif use_flash:
        from megatron_llm_tpu.ops.pallas.flash_attention import (
            sharded_flash_attention,
        )

        # under a mesh the Mosaic kernel must run in an explicit
        # shard_map (GSPMD cannot auto-partition it); no mesh -> plain
        ctx = sharded_flash_attention(
            q, k, v,
            causal=True,
            sliding_window=window,
            softmax_scale=scale,
        )
    else:
        from megatron_llm_tpu.ops.chunked_attention import (
            CHUNKED_ATTENTION_MIN_SEQ,
            chunked_causal_attention,
        )

        # long-context XLA fallback: the [s, s] score tensor of the plain
        # path failed to compile at seq >= 4096 on one v5e (commit
        # `128e754`, not re-measured), which would turn a flash-kernel
        # degradation into a dead run exactly when the fallback matters;
        # the q-chunked path is exact and bounds score memory per chunk
        if flash_eligible and q.shape[1] >= CHUNKED_ATTENTION_MIN_SEQ:
            ctx = chunked_causal_attention(
                q, k, v,
                causal=True,
                sliding_window=window,
                softmax_scale=scale,
            )
        else:
            ctx = core_attention(q, k, v, cfg, attention_mask, dropout_key,
                                 train, window, scale)

    b, s = ctx.shape[:2]
    if gate is not None:
        # every path above ends here: the paged cache's, the legacy
        # caches', flash, ring, chunked and the plain one
        with jax.named_scope("attn_gate"):
            ctx = ctx.reshape(b, s, cfg.num_attention_heads, cfg.head_dim)
            ctx = ctx * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(ctx.dtype)
    ctx = ctx.reshape(b, s, cfg.num_attention_heads * cfg.head_dim)
    out = row_parallel_linear(
        ctx, params["dense"],
        in_logical="heads",
        sequence_parallel=sequence_parallel,
        compute_dtype=cfg.compute_jnp_dtype,
    )
    if kv_cache is not None:
        return out, new_cache
    return out


def cross_attention(
    x: jax.Array,
    encoder_output: jax.Array,
    params,
    cfg: TransformerConfig,
    *,
    enc_dec_mask: Optional[jax.Array],
    dropout_key: Optional[jax.Array],
    train: bool,
    sequence_parallel: bool = False,
) -> jax.Array:
    """Encoder-decoder attention (reference ``ParallelAttention`` with
    ``AttnType.cross_attn``, transformer.py:344-365,466-476): Q from the
    decoder stream, packed KV from the encoder output, full head count.

    ``enc_dec_mask``: [b, 1, sq, sk] bool, True = masked away; None attends
    everywhere."""
    nh, d = cfg.num_attention_heads, cfg.head_dim
    q = column_parallel_linear(
        x, params["query"],
        out_logical="heads",
        sequence_parallel=sequence_parallel,
        compute_dtype=cfg.compute_jnp_dtype,
    )
    kv = column_parallel_linear(
        encoder_output, params["key_value"],
        out_logical="heads",
        sequence_parallel=sequence_parallel,
        compute_dtype=cfg.compute_jnp_dtype,
    )
    b, sq = x.shape[:2]
    sk = encoder_output.shape[1]
    q = q.reshape(b, sq, nh, d)
    # packed [nh, 2*d] layout, first d = K (reference splits 2*hn in half,
    # transformer.py:471-476)
    kv = kv.reshape(b, sk, nh, 2, d)
    k = kv[:, :, :, 0, :]
    v = kv[:, :, :, 1, :]

    if enc_dec_mask is None:
        enc_dec_mask = jnp.zeros((1, 1, sq, sk), jnp.bool_)
    ctx = core_attention(q, k, v, cfg, enc_dec_mask, dropout_key, train)
    ctx = ctx.reshape(b, sq, nh * d)
    return row_parallel_linear(
        ctx, params["dense"],
        in_logical="heads",
        sequence_parallel=sequence_parallel,
        compute_dtype=cfg.compute_jnp_dtype,
    )


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp(
    x: jax.Array,
    params,
    cfg: TransformerConfig,
    *,
    sequence_parallel: bool = False,
) -> jax.Array:
    """Reference ``ParallelMLP`` (transformer.py:77-141): column-parallel
    h->ffn (doubled under GLU), activation, row-parallel ffn->h.

    The first projection's kernel says by its rank where a GLU's two
    halves live (``parallel/glu_pairs.py``): flat ``[h, 2F]`` stored
    ``[gate | up]``, or the trainer's ``[2, h, F]`` with the pair an axis
    of its own (the product then has it too, ``[..., 2, F]``), so that
    under tp a shard of ``F`` holds both halves of its columns and
    ``act(gate) * up`` moves nothing."""
    h = column_parallel_linear(
        x, params["dense_h_to_4h"],
        out_logical="ffn",
        sequence_parallel=sequence_parallel,
        compute_dtype=cfg.compute_jnp_dtype,
    )
    h = apply_mlp_activation(h, cfg, paired=h.ndim > x.ndim)
    return row_parallel_linear(
        h, params["dense_4h_to_h"],
        in_logical="ffn",
        sequence_parallel=sequence_parallel,
        compute_dtype=cfg.compute_jnp_dtype,
    )


# ---------------------------------------------------------------------------
# Layer
# ---------------------------------------------------------------------------

def _dropout(x, rate, key, train):
    if not train or key is None:
        return x
    if isinstance(rate, (float, int)):
        if rate <= 0.0:
            return x
        keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
        return x * keep.astype(x.dtype) / (1.0 - rate)
    # traced per-layer rate (lima dropout under scan)
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    dropped = x * keep.astype(x.dtype) / jnp.maximum(1.0 - rate, 1e-6).astype(x.dtype)
    return jnp.where(rate > 0.0, dropped, x)


def transformer_layer(
    x: jax.Array,
    params,
    cfg: TransformerConfig,
    *,
    freqs=None,
    attention_mask=None,
    position_ids=None,
    rng_key=None,
    train: bool = False,
    sequence_parallel: bool = False,
    hidden_dropout: Optional[float] = None,
    kv_cache=None,
    encoder_output: Optional[jax.Array] = None,
    enc_dec_mask: Optional[jax.Array] = None,
    moe_layer: Optional[int] = None,
    layer_type: Optional[str] = None,
):
    """One decoder layer (reference ``ParallelTransformerLayer``,
    transformer.py:612-846), supporting:

    * pre-LN (default) and post-LN (``use_post_ln``, :660-664)
    * Falcon parallel attention+MLP (``parallel_attn``, :635-664,804-845)
      with optional separate MLP layernorm (``parallel_layernorm``)
    * per-layer hidden dropout override (lima dropout, :765-777)
    * T5-style cross-attention when the layer has ``inter_attention`` params
      and ``encoder_output`` is given (``LayerType.decoder``, :695-714,813-825)

    Returns the fixed-arity triple ``(out, new_cache, moe_aux)`` —
    ``new_cache`` is None when ``kv_cache`` is None, ``moe_aux`` is None
    for a layer with a dense MLP (what ``params['mlp']`` holds decides).  With ``moe_layer`` the experts' weights
    in ``params`` are every layer's, stacked, and this layer is that one
    of them (``moe_mlp_dropless``).  ``layer_type``: the layer's, of a
    model with ``cfg.layer_types`` (``attention`` says what it decides);
    a ``'mamba'`` layer's mixer is ``models/mamba.py::mamba_mixer`` over
    ``params['mamba']`` in place of attention, a ``'conv'`` layer's
    ``models/short_conv.py::short_conv_mixer`` over ``params['conv']``, a
    ``'retention'`` layer's ``models/retention.py::retention_mixer`` over
    ``params['retention']``, a ``'gated_delta'`` layer's
    ``models/gated_delta.py::gated_delta_mixer`` over
    ``params['gated_delta']``.  Both residual branches
    are multiplied by ``cfg.residual_multiplier``.  In a stack of ONE
    sublayer a layer (``cfg.one_sublayer``) the layer is
    ``x + f(input_norm(x))``, ``f`` its mixer or, for the type
    ``'moe'``, the expert layer over ``params['moe']``, whose cache (a
    ``PagedKVCache`` of no pool) carries the step's live rows in and the
    routing histogram out.
    """
    is_decoder = "inter_attention" in params and encoder_output is not None
    if is_decoder and cfg.parallel_attn:
        raise NotImplementedError(
            "cross-attention (T5 decoder) is not supported with parallel_attn"
        )
    if hidden_dropout is None:
        hidden_dropout = cfg.hidden_dropout
    # NB: the split count depends only on static pytree structure, so
    # decoder-only models keep their pre-existing dropout streams
    k_x_drop = k_hx = None
    if rng_key is not None:
        if is_decoder:
            k_attn_drop, k_h1, k_h2, k_x_drop, k_hx = jax.random.split(rng_key, 5)
        else:
            k_attn_drop, k_h1, k_h2 = jax.random.split(rng_key, 3)
    else:
        k_attn_drop = k_h1 = k_h2 = None

    norm = lambda h, p: apply_norm(
        h, p, cfg.normalization, eps=cfg.layernorm_epsilon,
        fp32_compute=cfg.norm_in_fp32,
        use_pallas=(
            (cfg.use_fused_rmsnorm and cfg.normalization == "rmsnorm")
            or (cfg.use_fused_layernorm and cfg.normalization == "layernorm")
        ),
    )

    residual = x
    ln_out = norm(x, params["input_norm"]) if not cfg.use_post_ln else x

    attn_kw = dict(
        freqs=freqs, attention_mask=attention_mask, position_ids=position_ids,
        dropout_key=k_attn_drop, train=train, sequence_parallel=sequence_parallel,
        kv_cache=kv_cache, layer_type=layer_type,
    )
    # named_scope: trace-time profiler annotation (telemetry.py --profile)
    if layer_type == "moe":
        # an expert layer alone: no mixer, and the cache goes through
        attn_out, new_cache = None, kv_cache
    elif layer_type == "mamba":
        from megatron_llm_tpu.models.mamba import mamba_mixer

        if train or attention_mask is not None:
            raise NotImplementedError(
                "state-space layers ('mamba') are not implemented for "
                "training (no backward through the chunked scan is held "
                "to anything) nor under an explicit attention mask (packed "
                "documents would need the state reset at each boundary)")
        with jax.named_scope("mamba"):
            attn_out = mamba_mixer(ln_out, params["mamba"], cfg,
                                   kv_cache=kv_cache)
            new_cache = None
            if kv_cache is not None:
                attn_out, new_cache = attn_out
    elif layer_type == "retention":
        from megatron_llm_tpu.models.retention import retention_mixer

        if attention_mask is not None:
            raise NotImplementedError(
                "power-retention layers ('retention') are not implemented "
                "under an explicit attention mask (packed documents would "
                "need the state reset at each boundary)")
        # the four projections are attention's, and so is their scope
        with jax.named_scope("attention"):
            attn_out = retention_mixer(
                ln_out, params["retention"], cfg, freqs=freqs,
                position_ids=position_ids, kv_cache=kv_cache)
        new_cache = None
        if kv_cache is not None:
            attn_out, new_cache = attn_out
    elif layer_type == "gated_delta":
        from megatron_llm_tpu.models.gated_delta import gated_delta_mixer

        if attention_mask is not None:
            raise NotImplementedError(
                "gated delta-rule layers ('gated_delta') are not "
                "implemented under an explicit attention mask (packed "
                "documents would need the state reset at each boundary)")
        attn_out = gated_delta_mixer(ln_out, params["gated_delta"], cfg,
                                     kv_cache=kv_cache)
        new_cache = None
        if kv_cache is not None:
            attn_out, new_cache = attn_out
    elif layer_type == "conv":
        from megatron_llm_tpu.models.short_conv import short_conv_mixer

        if attention_mask is not None:
            raise NotImplementedError(
                "gated short-convolution layers ('conv') are not "
                "implemented under an explicit attention mask (packed "
                "documents would need the carried columns reset at each "
                "boundary)")
        attn_out = short_conv_mixer(ln_out, params["conv"], cfg,
                                    kv_cache=kv_cache)
        new_cache = None
        if kv_cache is not None:
            attn_out, new_cache = attn_out
    else:
        with jax.named_scope("attention"):
            if kv_cache is not None:
                attn_out, new_cache = attention(
                    ln_out, params["attention"], cfg, **attn_kw)
            else:
                attn_out = attention(ln_out, params["attention"], cfg,
                                     **attn_kw)
                new_cache = None
    if cfg.sublayer_output_norm:
        # x + norm(f(norm(x))): the mixer's OUTPUT is normed too
        with jax.named_scope("post_attn_norm"):
            attn_out = norm(attn_out, params["attention_output_norm"])
    if cfg.residual_multiplier != 1.0:
        attn_out = attn_out * jnp.asarray(cfg.residual_multiplier,
                                          attn_out.dtype)
    if cfg.one_sublayer and attn_out is not None:
        # a mixer layer of a stack of one sublayer a layer ends here
        return residual + attn_out, new_cache, None
    mlp_params = params["moe" if cfg.one_sublayer else "mlp"]

    # MoE (num_experts > 1) replaces the dense MLP and adds a routing aux
    # loss threaded up through the stack scan (models/moe.py): the
    # capacity einsum when training, the dropless path otherwise.  Under
    # the paged cache the step's live tokens are the first valid_lens of
    # each row; the others are routed nowhere, and the histogram of live
    # assignments goes out in the cache's declared field for the engine's
    # counters.
    def run_mlp(inp):
        nonlocal new_cache
        with jax.named_scope("mlp"):
            if "experts" not in mlp_params:
                # a dense model, or a sparse model's leading dense layer
                return mlp(inp, mlp_params, cfg,
                           sequence_parallel=sequence_parallel), None
            if train:
                return moe_mlp(inp, mlp_params, cfg)
            paged = isinstance(new_cache, PagedKVCache)
            live = new_cache.live(inp.shape[1]) if paged else None
            out, aux, counts = moe_mlp_dropless(inp, mlp_params, cfg,
                                                live, moe_layer)
            if paged:
                new_cache = dataclasses.replace(new_cache,
                                                moe_counts=counts)
            return out, aux

    if cfg.one_sublayer:
        # an expert layer alone, under the layer's one norm
        mlp_out, moe_aux = run_mlp(ln_out)
        if cfg.residual_multiplier != 1.0:
            mlp_out = mlp_out * jnp.asarray(cfg.residual_multiplier,
                                            mlp_out.dtype)
        return residual + mlp_out, new_cache, moe_aux

    if cfg.parallel_attn:
        # Falcon: mlp feeds from the same (or its own) LN output; single
        # residual add of attn + mlp (reference: transformer.py:811-845)
        if cfg.parallel_layernorm:
            mlp_in = norm(x, params["mlp_norm"])
        else:
            mlp_in = ln_out
        mlp_out, moe_aux = run_mlp(mlp_in)
        out = residual + _dropout(
            attn_out + mlp_out, hidden_dropout, k_h1, train
        )
        if cfg.use_post_ln:
            out = norm(out, params["input_norm"])
        return out, new_cache, moe_aux

    # sequential: attn -> residual -> ln [-> cross-attn -> residual -> ln]
    # -> mlp -> residual
    h = residual + _dropout(attn_out, hidden_dropout, k_h1, train)
    if cfg.use_post_ln:
        h = norm(h, params["input_norm"])
    residual = h
    ln2 = norm(h, params["post_attention_norm"]) if not cfg.use_post_ln else h
    if is_decoder:
        # reference: transformer.py:813-825
        inter_out = cross_attention(
            ln2, encoder_output, params["inter_attention"], cfg,
            enc_dec_mask=enc_dec_mask, dropout_key=k_x_drop, train=train,
            sequence_parallel=sequence_parallel,
        )
        h = residual + _dropout(inter_out, hidden_dropout, k_hx, train)
        if cfg.use_post_ln:
            h = norm(h, params["post_attention_norm"])
        residual = h
        ln2 = (
            norm(h, params["post_inter_attention_norm"])
            if not cfg.use_post_ln else h
        )
    mlp_out, moe_aux = run_mlp(ln2)
    if cfg.sublayer_output_norm:
        with jax.named_scope("post_mlp_norm"):
            mlp_out = norm(mlp_out, params["mlp_output_norm"])
    if cfg.residual_multiplier != 1.0:
        mlp_out = mlp_out * jnp.asarray(cfg.residual_multiplier,
                                        mlp_out.dtype)
    out = residual + _dropout(mlp_out, hidden_dropout, k_h2, train)
    if cfg.use_post_ln:
        out = norm(
            out,
            params["post_inter_attention_norm" if is_decoder
                   else "post_attention_norm"],
        )
    return out, new_cache, moe_aux


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------

def _lima_dropout_rates(cfg: TransformerConfig):
    """LIMA-style linearly increasing layer dropout p_l = p * l / (L-1)
    (reference: --lima_dropout, transformer.py:765-777)."""
    L = cfg.num_layers
    if L == 1:
        return jnp.zeros((1,), jnp.float32)
    return cfg.hidden_dropout * jnp.arange(L, dtype=jnp.float32) / (L - 1)


def transformer_stack(
    x: jax.Array,
    stack_params,
    cfg: TransformerConfig,
    *,
    freqs=None,
    attention_mask=None,
    position_ids=None,
    rng_key=None,
    train: bool = False,
    sequence_parallel: bool = False,
    kv_caches=None,
    encoder_output: Optional[jax.Array] = None,
    enc_dec_mask: Optional[jax.Array] = None,
    return_exit: bool = False,
):
    """Scan the layer body over layer-stacked params (reference
    ``ParallelTransformer.forward``, transformer.py:1188-1282) and apply the
    final norm.  Recompute policy per cfg.recompute_granularity
    (:1110-1176): 'uniform'/'block' -> full per-layer remat; 'selective' ->
    save-nothing-but-matmul-free recompute of core attention via policy.

    A sparse model's leading dense layers (``dense_layers`` of the
    params) run before the scan, which is over the sparse layers; under
    ``cfg.layer_types`` each is of the type its index in the WHOLE stack
    gives it, and the sparse layers that finish the period the dense
    ones began run before the scan too, which then starts at a period's
    first layer.

    A model with a layer type per layer (``cfg.layer_types``: one period
    of types) scans over PERIODS with a period's layers unrolled in the
    body, each of its own type, so the trace holds one period whatever
    the depth; a model of one type is one period of one layer.  Where
    the period's mixers are of two kinds (``cfg.mixers_by_kind``) their
    parameters are stacked apart (``init_stack_params``) and a layer
    takes its own by its index AMONG ITS KIND over the WHOLE depth
    (``cfg.mixer_index``), in the layers before the scan, in the scan
    and in the serving loop alike, a leading dense layer too; so are
    the three kinds of a stack of one sublayer a layer
    (``cfg.one_sublayer``), its expert layers among them.

    A LOOPED stack (``cfg.loop_steps`` T > 1) runs all of that T times
    over the same parameters, the final norm after EACH pass, whose
    output is the next pass's input; with ``kv_caches`` pass t's layer i
    threads cache ``t * L + i`` (``cfg.cache_layers`` of them), so a pass
    attends its own keys and values alone.  ``return_exit`` (the scan
    path): ``(h, gates)``, gates ``[T, b, s]`` float32 the exit gate's
    sigmoid over each pass's normed stream (``exit_distribution``)."""
    layers = stack_params["layers"]
    L, T = cfg.num_layers, cfg.loop_steps
    said = train and refusal(cfg, (TRAINING,))
    if said:
        raise NotImplementedError(said)
    # the mixers of a stack with state-space layers (the three kinds of a
    # stack of one sublayer a layer), by kind, apart from the leaves
    # every layer has
    mixers = {k: layers[k] for k in cfg.mixer_counts}
    if mixers:
        layers = {k: v for k, v in layers.items() if k not in mixers}
    # a sparse model's leading dense layers: other leaves, so stacked
    # apart (``init_stack_params``) and run before the scan; ``layers``
    # holds the L - D that follow
    dense = stack_params.get("dense_layers")
    D = cfg.moe_first_dense_layers if dense is not None else 0
    period = cfg.layer_period
    P = len(period)
    # Per-layer dropout rates are traced (scanned) only for lima dropout;
    # otherwise the static config rate short-circuits at trace time.
    dropout_rates = _lima_dropout_rates(cfg) if cfg.lima_dropout else None
    layer_keys = (
        jax.random.split(rng_key, L) if rng_key is not None else jnp.zeros((L, 2), jnp.uint32)
    )

    moe_on = cfg.num_experts > 1
    layer_kw = dict(
        freqs=freqs, attention_mask=attention_mask, position_ids=position_ids,
        sequence_parallel=sequence_parallel)

    @jax.named_scope("transformer_layer")
    def body(carry, scanned):
        h, aux_acc = carry if moe_on else (carry, None)
        scanned, mixers_p = scanned if mixers else (scanned, None)
        for j, layer_type in enumerate(period):
            # a period's layer j: scanned leaves are [P, ...] there
            one = (scanned if P == 1 else
                   jax.tree_util.tree_map(lambda a: a[j], scanned))
            if dropout_rates is not None:
                layer_p, key, rate = one
            else:
                layer_p, key = one
                rate = None
            if mixers:
                # its mixer: the period's layers of its kind before it
                at = period[:j].count(layer_type)
                layer_p = {**layer_p, layer_type: jax.tree_util.tree_map(
                    lambda a: a[at], mixers_p[layer_type])}
            h, _, moe_aux = transformer_layer(
                h, layer_p, cfg,
                rng_key=key if rng_key is not None else None,
                train=train, hidden_dropout=rate,
                encoder_output=encoder_output, enc_dec_mask=enc_dec_mask,
                layer_type=layer_type, **layer_kw,
            )
            if moe_aux is not None:
                aux_acc = aux_acc + moe_aux
        return ((h, aux_acc) if moe_on else h), None

    if cfg.recompute_granularity in ("uniform", "block", "full"):
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.recompute_granularity == "selective":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )

    def final_norm(h):
        return apply_norm(
            h, stack_params["final_norm"], cfg.normalization,
            eps=cfg.layernorm_epsilon, fp32_compute=cfg.norm_in_fp32,
        )

    def looped(*names):
        """The named scopes ``names`` of a looped stack, one inside the
        other: ``loop_pass`` (which the program tables know) around
        ``pass_<t>``, ``loop_pass_norm`` for the norm that ends EACH
        pass.  None for a stack run once: its programs are what they
        were."""
        scopes = contextlib.ExitStack()
        for name in names if T > 1 else ():
            scopes.enter_context(jax.named_scope(name))
        return scopes

    if kv_caches is not None:
        # inference path: python loop so each layer threads its own cache
        # (MoE aux, when present, is irrelevant at decode time and dropped)
        new_caches = []
        h = x
        # a sparse model's experts go in whole, as the model stacks them,
        # with the layer's index AMONG THE SPARSE LAYERS: models/moe.py
        # says why, and decides what to do with them
        sliced = layers
        if moe_on and not cfg.one_sublayer:
            sliced = {**layers, "mlp": {k: v for k, v in layers["mlp"].items()
                                        if k != "experts"}}

        @functools.cache
        def layer_of(i):
            """(layer i's parameters, its index among the layers with
            experts or None): sliced when its first pass reaches it, and
            the same slices for every later pass."""
            sparse = moe_on and i >= D and not cfg.one_sublayer
            moe_layer = i - D if sparse else None
            layer_p = jax.tree_util.tree_map(
                lambda p: p[i - D], sliced) if i >= D else (
                jax.tree_util.tree_map(lambda p: p[i], dense))
            if sparse:
                layer_p["mlp"]["experts"] = layers["mlp"]["experts"]
            if mixers:
                kind, at = cfg.mixer_index(i)
                own = dict(mixers[kind])
                # an expert layer alone: the experts whole, as above,
                # this layer ``at`` of them
                whole = {"experts": own.pop("experts")} if kind == "moe" else {}
                moe_layer = at if whole else moe_layer
                layer_p[kind] = {**jax.tree_util.tree_map(
                    lambda p: p[at], own), **whole}
            return layer_p, moe_layer

        for t, i in itertools.product(range(T), range(L)):
            layer_p, moe_layer = layer_of(i)
            with looped("loop_pass", f"pass_{t}"):
                h, c, _ = transformer_layer(
                    h, layer_p, cfg, rng_key=None, train=False,
                    kv_cache=kv_caches[t * L + i], moe_layer=moe_layer,
                    layer_type=period[i % P], **layer_kw,
                )
            new_caches.append(c)
            if i == L - 1:
                with looped("loop_pass_norm"):
                    h = final_norm(h)
        return h, new_caches

    # the layers before the first period boundary that the scan starts
    # at, unrolled, each of its own type: the leading dense layers (they
    # attend like any layer of their type), then the ``head`` sparse
    # layers that finish the period the dense ones began (afmoe: 2 dense
    # and 2 sparse, then whole periods of 4)
    head = (P - D % P) % P
    aux_head = jnp.zeros((2,), jnp.float32)
    for i in range(D + head):
        stacked, at = (dense, i) if i < D else (layers, i - D)
        layer_p = jax.tree_util.tree_map(lambda p: p[at], stacked)
        if mixers:
            kind, own = cfg.mixer_index(i)
            layer_p[kind] = jax.tree_util.tree_map(lambda p: p[own],
                                                   mixers[kind])
        x, _, moe_aux = transformer_layer(
            x, layer_p, cfg,
            rng_key=layer_keys[i] if rng_key is not None else None,
            train=train,
            hidden_dropout=(dropout_rates[i] if dropout_rates is not None
                            else None),
            encoder_output=encoder_output, enc_dec_mask=enc_dec_mask,
            layer_type=period[i % P], **layer_kw,
        )
        if moe_aux is not None:
            aux_head = aux_head + moe_aux
    first = D + head
    scanned = (
        (layers, layer_keys[D:], dropout_rates[D:])
        if dropout_rates is not None
        else (layers, layer_keys[D:])
    )
    if head:
        scanned = jax.tree_util.tree_map(lambda a: a[head:], scanned)
    if P > 1:
        # [L - first, ...] -> [(L - first) / P, P, ...]: the scan's step
        # is a period
        scanned = jax.tree_util.tree_map(
            lambda a: a.reshape(((L - first) // P, P) + a.shape[1:]),
            scanned)
    if mixers:
        # a kind's [its layers after the first, ...] -> [periods, its
        # layers a period, ...]
        before = [cfg.mixer_index(i)[0] for i in range(first)]
        scanned = (scanned, {
            k: jax.tree_util.tree_map(
                lambda a: (a[before.count(k):] if first else a).reshape(
                    ((L - first) // P, period.count(k)) + a.shape[1:]), m)
            for k, m in mixers.items()})
    carry = (x, aux_head) if moe_on else x
    gates = []
    for t in range(T):
        if first < L:
            with looped("loop_pass", f"pass_{t}"):
                carry, _ = jax.lax.scan(body, carry, scanned)
        h, moe_aux = carry if moe_on else (carry, None)
        with looped("loop_pass_norm"):
            h = final_norm(h)
        carry = (h, moe_aux) if moe_on else h
        if return_exit:
            gates.append(exit_gate(h, stack_params["exit_gate"]))
    if return_exit:
        return h, jnp.stack(gates)
    return (h, moe_aux) if moe_on else h


def exit_gate(h: jax.Array, gate) -> jax.Array:
    """A looped stack's exit gate over a pass's normed stream ``h``
    ``[..., hidden]``: ``sigmoid(h . w + b)`` in float32, ``[...]``."""
    return jax.nn.sigmoid(
        jnp.einsum("...h,h->...", h.astype(jnp.float32),
                   gate["kernel"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
        + gate["bias"].astype(jnp.float32))


def exit_distribution(gates: jax.Array) -> jax.Array:
    """The exit distribution over a looped stack's T passes from its
    gates ``[T, ...]``: ``p_t = gate_t * prod_{i<t} (1 - gate_i)`` for
    t < T and the last pass takes what is left, so they sum to 1."""
    stay = jnp.cumprod(1.0 - gates[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([gates[:-1] * before, stay[-1:]], axis=0)


def rotary_freqs(cfg: TransformerConfig, seq_len: Optional[int] = None):
    """The stack's rotary table (cos, sin); None where there is none: no
    rotary embedding, or a layer type per layer, whose layers rotate at
    the given positions by their own type's variant (``attention``)."""
    if (cfg.position_embedding_type != PositionEmbeddingType.rotary
            or cfg.layer_types is not None):
        return None
    rot_d = int(cfg.head_dim * cfg.rotary_percent)
    rot_d -= rot_d % 2
    l3 = cfg.rope_llama3_scaling
    return precompute_freqs_cis(
        rot_d,
        seq_len or cfg.max_position_embeddings,
        theta=cfg.rope_theta,
        scaling_factor=cfg.rope_scaling_factor,
        llama3_scaling=(dict(zip(
            ("factor", "low_freq_factor", "high_freq_factor",
             "original_max_position"), l3)) if l3 else None),
        yarn=cfg.rope_yarn_scaling,
    )
