"""Embedding + transformer + LM head.

Reference: ``megatron/model/language_model.py`` — ``Embedding`` (:163-262,
vocab-parallel word embedding + optional learned absolute position
embedding + embedding dropout with the sequence-parallel scatter at
:255-258), ``TransformerLanguageModel`` (:488+), ``parallel_lm_logits``
(:24-53), untied lm_head (:436-457), and the per-forward FLOP estimate
(:370-384) used for MFU accounting.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import TransformerConfig, PositionEmbeddingType
from megatron_llm_tpu.parallel.layers import (
    init_embedding_params,
    init_method_for,
    init_method_normal,
    parallel_lm_logits,
    vocab_parallel_embedding,
)
from megatron_llm_tpu.parallel.sharding import constrain
from megatron_llm_tpu.models.moe import moe_mlp_specs
from megatron_llm_tpu.models.transformer import (
    init_stack_params,
    rotary_freqs,
    transformer_stack,
)
from megatron_llm_tpu import random as mrandom


def init_language_model_params(key, cfg: TransformerConfig, dtype=None):
    """Param pytree:

    {
      'embedding': {'word': {'embedding': [V, H]},
                    'position'?: {'embedding': [P, H]}},
      'transformer': {'layers': {...stacked [L, ...]}, 'final_norm': {...}},
      'lm_head'?: {'weight': [V, H]}   (when not tie_embed_logits)
    }
    """
    dtype = dtype or cfg.params_jnp_dtype
    k_emb, k_pos, k_stack, k_head = jax.random.split(key, 4)
    init = init_method_for(cfg)
    params = {
        "embedding": {
            "word": init_embedding_params(
                k_emb, cfg.padded_vocab_size, cfg.hidden_size,
                init_method=init, dtype=dtype,
            )
        },
        "transformer": init_stack_params(k_stack, cfg, dtype),
    }
    if cfg.position_embedding_type == PositionEmbeddingType.learned_absolute:
        params["embedding"]["position"] = init_embedding_params(
            k_pos, cfg.max_position_embeddings, cfg.hidden_size,
            init_method=init, dtype=dtype,
        )
    if cfg.num_tokentypes > 0:
        # segment embeddings (reference: language_model.py:188-199)
        k_tok = jax.random.fold_in(k_pos, 1)
        params["embedding"]["tokentype"] = init_embedding_params(
            k_tok, cfg.num_tokentypes, cfg.hidden_size,
            init_method=init, dtype=dtype,
        )
    if not cfg.tie_embed_logits:
        # untied lm_head parameter (reference: language_model.py:436-457)
        params["lm_head"] = {
            "weight": init(k_head, (cfg.padded_vocab_size, cfg.hidden_size), dtype)
        }
    return params


def _linear_spec(p, in_ax, out_ax, stacked):
    lead = ("stage",) if stacked else ()
    # a gated MLP's first projection held paired, [.., 2, in, out]
    # (parallel/glu_pairs.py): the pair axis is nobody's shard
    kernel = p.get("kernel")
    pair = (None,) if getattr(kernel, "ndim", 0) == len(lead) + 3 else ()
    spec = {"kernel": lead + pair + (in_ax, out_ax)}
    if "bias" in p:
        spec["bias"] = lead + pair + (out_ax,)
    return spec


def _norm_spec(p, stacked):
    lead = ("stage",) if stacked else ()
    return {k: lead + (None,) for k in p}


def transformer_layer_specs(layers, stacked: bool = True, cfg=None) -> dict:
    """Logical-axis specs for one (layer-stacked) transformer layer pytree,
    including the decoder ``inter_attention`` block when present.  ``cfg``
    (when given) carries the resolved ``moe_expert_axis`` so MoE specs
    don't re-derive placement from the live mesh."""
    attn = layers.get("attention")
    if attn is None:
        # a stack of state-space layers alone has no attention leaves
        attention_specs = None
    elif "kv_down" in attn:
        # latent attention is replicated (tp is refused)
        attention_specs = {
            **{name: _linear_spec(attn[name], None, None, stacked)
               for name in ("query", "query_down", "kv_down", "kv_up",
                            "dense") if name in attn},
            **{name: _norm_spec(attn[name], stacked)
               for name in ("kv_norm", "query_norm") if name in attn},
        }
    else:
        attention_specs = {
            "query_key_value": _linear_spec(
                attn["query_key_value"], None, "heads", stacked
            ),
            "dense": _linear_spec(attn["dense"], "heads", None, stacked),
        }
    layer_specs = {
        "input_norm": _norm_spec(layers["input_norm"], stacked),
        **({} if attention_specs is None
           else {"attention": attention_specs}),
        # a state-space, short-convolution, retention or delta-rule
        # mixer is replicated (tp is refused)
        **{kind: jax.tree_util.tree_map(
            lambda a: (("stage",) if stacked else ())
            + (None,) * (a.ndim - int(stacked)), layers[kind])
           for kind in ("mamba", "conv", "retention", "gated_delta")
           if kind in layers},
    }
    if "moe" in layers:
        # the expert layers of a stack of one sublayer a layer
        layer_specs["moe"] = moe_mlp_specs(layers["moe"], stacked, cfg=cfg)
    else:
        layer_specs["mlp"] = (
            moe_mlp_specs(layers["mlp"], stacked, cfg=cfg)
            if "experts" in layers["mlp"]
            else {
                "dense_h_to_4h": _linear_spec(
                    layers["mlp"]["dense_h_to_4h"], None, "ffn", stacked
                ),
                "dense_4h_to_h": _linear_spec(
                    layers["mlp"]["dense_4h_to_h"], "ffn", None, stacked
                ),
            }
        )
    for name in ("q_norm", "k_norm"):
        if attn is not None and name in attn:
            layer_specs["attention"][name] = _norm_spec(
                layers["attention"][name], stacked)
    if attn is not None and "indexer" in attn:
        # the sparse-attention indexer is replicated (tp is refused)
        ix = layers["attention"]["indexer"]
        layer_specs["attention"]["indexer"] = {
            **{name: _linear_spec(ix[name], None, None, stacked)
               for name in ("query", "key", "weights")},
            "key_norm": _norm_spec(ix["key_norm"], stacked),
        }
    if "post_attention_norm" in layers:
        layer_specs["post_attention_norm"] = _norm_spec(
            layers["post_attention_norm"], stacked
        )
    for name in ("mlp_norm", "attention_output_norm", "mlp_output_norm"):
        if name in layers:
            layer_specs[name] = _norm_spec(layers[name], stacked)
    if "inter_attention" in layers:
        ia = layers["inter_attention"]
        layer_specs["inter_attention"] = {
            "query": _linear_spec(ia["query"], None, "heads", stacked),
            "key_value": _linear_spec(ia["key_value"], None, "heads", stacked),
            "dense": _linear_spec(ia["dense"], "heads", None, stacked),
        }
        layer_specs["post_inter_attention_norm"] = _norm_spec(
            layers["post_inter_attention_norm"], stacked
        )
    return layer_specs


def transformer_stack_specs(stack_params, cfg=None) -> dict:
    specs = {
        "layers": transformer_layer_specs(stack_params["layers"], cfg=cfg),
        "final_norm": _norm_spec(stack_params["final_norm"], False),
    }
    if "dense_layers" in stack_params:
        # a sparse model's leading dense layers, stacked apart
        specs["dense_layers"] = transformer_layer_specs(
            stack_params["dense_layers"], cfg=cfg)
    if "exit_gate" in stack_params:
        # a looped stack's exit gate: a vector of the hidden width and a
        # scalar, on every device whole
        specs["exit_gate"] = {"kernel": (None,), "bias": ()}
    return specs


def language_model_param_specs(params, cfg: TransformerConfig):
    """Logical-axis spec pytree matching ``init_language_model_params``
    (consumed by ``parallel.sharding.shard_params``)."""
    specs = {
        "embedding": {"word": {"embedding": ("vocab", None)}},
        "transformer": transformer_stack_specs(params["transformer"],
                                               cfg=cfg),
    }
    if "position" in params["embedding"]:
        specs["embedding"]["position"] = {"embedding": (None, None)}
    if "tokentype" in params["embedding"]:
        specs["embedding"]["tokentype"] = {"embedding": (None, None)}
    if "lm_head" in params:
        specs["lm_head"] = {"weight": ("vocab", None)}
    return specs


@jax.custom_vjp
def scatter_free_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """Embedding lookup whose backward is a one-hot einsum instead of the
    gather transpose (scatter-add).  XLA's scatter partitioner check-fails
    under a manual submesh (used by the pipeline engines); the matmul
    transpose partitions robustly and is head-matmul-sized."""
    return jnp.take(table, tokens, axis=0)


def _sfl_fwd(table, tokens):
    return jnp.take(table, tokens, axis=0), (table.shape[0], tokens)


def _sfl_bwd(res, g):
    vocab, tokens = res
    one_hot = jax.nn.one_hot(tokens, vocab, dtype=g.dtype)
    return jnp.einsum("...v,...h->vh", one_hot, g), None


scatter_free_lookup.defvjp(_sfl_fwd, _sfl_bwd)


def vocab_parallel_lookup_manual(table: jax.Array,
                                 tokens: jax.Array) -> jax.Array:
    """Reference ``VocabParallelEmbedding`` semantics written out by hand
    (``megatron/core/tensor_parallel/layers.py:128-210``): mask ids
    outside this tp-rank's vocab range, look up in the local shard, zero
    the masked rows, allreduce over tp — as a nested tp-manual shard_map.

    For call sites already inside a pp-manual shard_map (the pipeline
    engines), where GSPMD's gather partitioner check-fails on a
    vocab-sharded operand (spmd_partitioner_util.cc:495).  The inner
    region manualizes tp so no gather/scatter partitioning happens at
    all; backward is the local one-hot einsum via
    ``scatter_free_lookup``, sized 1/tp of a head matmul."""
    from jax.sharding import PartitionSpec as P

    from megatron_llm_tpu import topology

    tp_axis = topology.TP_AXIS
    # the call site sits inside a pp-manual shard_map: the nested region
    # must use the *context* (abstract) mesh, and names tp alone
    am, _ = topology.nesting_mesh(tp_axis)
    if am is None:
        return scatter_free_lookup(table, tokens)

    def local(table_l, toks):
        vl = table_l.shape[0]
        start = jax.lax.axis_index(tp_axis) * vl
        ids = toks - start
        valid = (ids >= 0) & (ids < vl)
        h = scatter_free_lookup(table_l, jnp.clip(ids, 0, vl - 1))
        h = jnp.where(valid[..., None], h, 0)
        return jax.lax.psum(h, tp_axis)

    return jax.shard_map(
        local,
        mesh=am,
        in_specs=(P(tp_axis, None), P()),
        out_specs=P(),
        axis_names={tp_axis},
        check_vma=False,
    )(table, tokens)


def embedding_forward(
    tokens: jax.Array,
    position_ids: Optional[jax.Array],
    params,
    cfg: TransformerConfig,
    *,
    tokentype_ids: Optional[jax.Array] = None,
    rng_key=None,
    train: bool = False,
    scatter_free: bool = False,
    vocab_parallel_manual: bool = False,
) -> jax.Array:
    """Word (+position, +tokentype) embedding with dropout; under sequence
    parallelism the output is scattered along the sequence axis
    (reference: language_model.py:230-262).  ``scatter_free`` swaps the
    word-lookup backward for the one-hot einsum; ``vocab_parallel_manual``
    additionally keeps the table vocab-sharded with a hand-written
    masked-lookup + tp-psum (pipeline engines)."""
    if vocab_parallel_manual:
        h = constrain(
            vocab_parallel_lookup_manual(
                params["word"]["embedding"].astype(cfg.compute_jnp_dtype),
                tokens,
            ),
            "batch", "seq", None,
        )
    elif scatter_free:
        h = constrain(
            scatter_free_lookup(
                params["word"]["embedding"].astype(cfg.compute_jnp_dtype),
                tokens,
            ),
            "batch", "seq", None,
        )
    else:
        h = vocab_parallel_embedding(
            tokens, params["word"], compute_dtype=cfg.compute_jnp_dtype
        )
    if cfg.embedding_multiplier is not None:
        # Gemma-style sqrt(hidden) normalizer on the embedding OUTPUT only
        # (the tied logits head reads the raw table)
        h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    if "position" in params:
        if position_ids is None:
            position_ids = jnp.arange(tokens.shape[1])[None, :]
        pos = jnp.take(
            params["position"]["embedding"].astype(cfg.compute_jnp_dtype),
            position_ids, axis=0,
        )
        h = h + pos
    if "tokentype" in params and tokentype_ids is not None:
        h = h + jnp.take(
            params["tokentype"]["embedding"].astype(cfg.compute_jnp_dtype),
            tokentype_ids, axis=0,
        )
    if train and cfg.hidden_dropout > 0.0 and rng_key is not None:
        keep = jax.random.bernoulli(rng_key, 1.0 - cfg.hidden_dropout, h.shape)
        h = h * keep.astype(h.dtype) / (1.0 - cfg.hidden_dropout)
    return h


def lm_head_weight(params) -> jax.Array:
    """[V, H] logits weight: the untied ``lm_head`` when present, else the
    tied word-embedding table (reference: language_model.py:24-53 picks the
    same way inside parallel_lm_logits' callers)."""
    if "lm_head" in params:
        return params["lm_head"]["weight"]
    return params["embedding"]["word"]["embedding"]


def lm_head_logits(params, h: jax.Array, cfg: TransformerConfig, *,
                   sequence_parallel: bool = False) -> jax.Array:
    """The output head over final hidden states ``h`` [..., H] -> logits
    [..., V] (vocab-sharded under tp): what ``language_model_forward``
    ends with, and what a caller that took ``compute_logits=False`` runs
    on the rows it reads (the serving engine's prefill chunk: one)."""
    with jax.named_scope("lm_head"):
        logits = parallel_lm_logits(
            h, lm_head_weight(params),
            sequence_parallel=sequence_parallel,
            compute_dtype=cfg.compute_jnp_dtype,
        )
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits


def language_model_forward(
    params,
    tokens: jax.Array,
    position_ids: Optional[jax.Array],
    attention_mask: Optional[jax.Array],
    cfg: TransformerConfig,
    *,
    tokentype_ids: Optional[jax.Array] = None,
    rng_key=None,
    train: bool = False,
    sequence_parallel: bool = False,
    compute_logits: bool = True,
    kv_caches=None,
    freqs=None,
):
    """Full LM forward -> logits [b, s, V] (vocab-sharded under tp) or the
    final hidden states when ``compute_logits=False``.

    Reference: TransformerLanguageModel.forward (language_model.py:488+)
    -> GPTModel.post_language_model_processing (gpt_model.py:21-41).
    """
    if rng_key is not None:
        k_embed, k_stack = jax.random.split(rng_key)
    else:
        k_embed = k_stack = None
    # named_scope: trace-time only (zero runtime cost) — groups the xplane
    # ops for the in-loop profiler (telemetry.py / --profile)
    with jax.named_scope("embedding"):
        h = embedding_forward(
            tokens, position_ids, params["embedding"], cfg,
            tokentype_ids=tokentype_ids, rng_key=k_embed, train=train,
        )
    if sequence_parallel:
        h = constrain(h, "batch", "seq_tp", None)
    if freqs is None:
        freqs = rotary_freqs(cfg, seq_len=None)

    if kv_caches is not None:
        h, new_caches = transformer_stack(
            h, params["transformer"], cfg,
            freqs=freqs, attention_mask=attention_mask, position_ids=position_ids,
            rng_key=None, train=False, sequence_parallel=sequence_parallel,
            kv_caches=kv_caches,
        )
    else:
        h = transformer_stack(
            h, params["transformer"], cfg,
            freqs=freqs, attention_mask=attention_mask, position_ids=position_ids,
            rng_key=k_stack, train=train, sequence_parallel=sequence_parallel,
        )
        new_caches = None
        if cfg.num_experts > 1:
            # MoE: the stack also returns the accumulated [lb, z] routing
            # aux losses; (x, aux) replaces x in every non-cache return
            h, moe_aux = h

    if not compute_logits:
        if kv_caches is not None:
            return h, new_caches
        return (h, moe_aux) if cfg.num_experts > 1 else h

    logits = lm_head_logits(params, h, cfg,
                            sequence_parallel=sequence_parallel)
    if kv_caches is not None:
        return logits, new_caches
    return (logits, moe_aux) if cfg.num_experts > 1 else logits


def flops_per_token(cfg: TransformerConfig, seq_len: Optional[int] = None) -> float:
    """Per-token fwd+bwd FLOPs for MFU accounting (reference FLOP estimate:
    language_model.py:370-384; 6ND approximation + attention term)."""
    s = seq_len or cfg.seq_length
    h = cfg.hidden_size
    # layer APPLICATIONS a token: a looped stack runs its layers
    # ``loop_steps`` times
    L = cfg.num_layers * cfg.loop_steps
    ffn = cfg.ffn_hidden_size
    ng = cfg.num_query_groups
    nh = cfg.num_attention_heads
    d = cfg.head_dim
    mult = 2 if cfg.glu_activation else 1
    # per layer matmul params: qkv + out proj + mlp
    qkv = h * (nh + 2 * ng) * d
    proj = nh * d * h
    mlp_p = h * ffn * mult + ffn * h
    if cfg.num_experts > 1:
        # MoE: top_k experts touched per token + the router matmul
        ffn = cfg.expert_hidden_size
        mlp_p = (cfg.moe_top_k * (h * ffn * mult + ffn * h)
                 + h * cfg.num_experts)
    dense = L * (qkv + proj + mlp_p)
    emb = cfg.padded_vocab_size * h
    # fwd = 2 flops/param/token, bwd = 4, attention = 2*2*s*nh*d per layer fwd
    attn = L * 2 * 2 * s * nh * d
    return 6.0 * (dense + emb) + 3.0 * attn
