"""Pipeline-parallel engine: compiled lock-step schedules over the ``pp``
mesh axis.

Reference: ``megatron/schedules.py`` (1F1B :606-722, interleaved :253-502)
+ ``megatron/p2p_communication.py`` (batched NCCL isend/irecv :101-251) +
layer-to-stage assignment (``megatron/model/transformer.py:1045-1090``) +
embedding-tie grad sync across first/last stages
(``megatron/optimizer/optimizer.py:203-229``).

TPU re-design — none of that machinery survives translation.  Two engines,
both a single jitted ``lax.scan`` over pipeline ticks inside a ``shard_map``
that is *manual over pp only* (dp/tp stay under GSPMD, so tensor-parallel
collectives inside each stage remain compiler-placed), with ``lax.ppermute``
as the p2p isend/irecv replacement:

1. **Streaming schedule** (``build_pipeline_loss_fn``) — autodiff engine,
   supports interleaved virtual pipelining (VPP).  Work items are
   (microbatch m, virtual chunk v) pairs; device k executes item
   ``w = g*S*V + v*S + r`` (mixed radix, m = g*S + r) at tick ``t = w + k``.
   The mapping is collision-free (each device runs exactly one chunk per
   tick) and gives the interleaved schedule's bubble, (S-1)/(M*V + S - 1)
   of fine ticks — the same 1/V bubble shrink as the reference's
   interleaved 1F1B (schedules.py:253-502).  Microbatch t's embedding is
   computed *inside* tick t on the first stage and cross entropy is
   streamed *inside* the tick on the last stage, so nothing of size
   O(M) or O(vocab x global-batch) is ever materialized.  Backward is
   autodiff through the scan (the transpose of ``ppermute`` is the
   reverse rotation); per-tick ``jax.checkpoint`` plus an outer blocked
   scan bound live activations to O(sqrt(T)) tick-carries.

2. **Manual 1F1B** (``build_pipeline_grad_fn``) — hand-written backward
   with the reference's O(S) in-flight activation cap
   (schedules.py:606-722).  Each tick does one forward chunk AND one
   backward chunk (the steady-state 1F1B rhythm); forward chunk inputs
   are stashed in a ring buffer of 2S slots, backward recomputes the
   chunk from the stashed input (``jax.vjp``) and accumulates parameter
   gradients in the scan carry.  Nothing is ever autodiffed through the
   scan, so activation memory is FLAT in the number of microbatches:
   carry = one fwd activation + one bwd cotangent + 2S stash slots +
   the gradient accumulators.  Backward of microbatch m runs on device k
   at tick ``m + 2S - 1 - k``; cotangents ride the reverse rotation.

* **Embedding and LM head live inside the shard_map** replicated over pp
  (still vocab-sharded over tp by GSPMD); every stage computes them each
  tick and the results are masked to the owning stage.  In lock-step SPMD
  the tick latency is the max over stages either way, which is exactly
  the reference's bottleneck (its last stage pays head+CE per microbatch).
* **Embedding tie**: the word embedding is one logical parameter used at
  ingest (lookup) and by the head (logits); in the autodiff engine its
  gradient sums both uses by linearity, in the manual engine both
  contributions are accumulated per stage and summed across pp outside
  the shard_map — the reference's embedding-group all-reduce
  (optimizer.py:203-229) has no analogue to write.  The word table stays
  **vocab-sharded over tp**: the lookup is ``vocab_parallel_lookup_manual``
  (masked local gather + tp-psum inside a nested tp-manual shard_map, the
  reference's VocabParallelEmbedding), with a local one-hot-einsum
  backward — XLA's gather/scatter partitioners, which check-fail on
  vocab-sharded operands under the manual submesh, never see it.

Layer-to-stage assignment is a *sharding spec*, not code: the stacked
layer axis [L, ...] is sharded over pp, giving each stage a contiguous
block of L/S rows.  For VPP the stacking order is **stage-major**
(device k's rows hold its V chunks contiguously, chunk v of device k =
natural layers [(v*S+k)*cl, (v*S+k+1)*cl)); use
``permute_layer_stack`` / ``unpermute_layer_stack`` to convert
(reference chunk math: transformer.py:1045-1090).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu import topology
from megatron_llm_tpu.config import TransformerConfig
from megatron_llm_tpu.models.language_model import (
    embedding_forward,
    lm_head_weight,
)
from megatron_llm_tpu.models.transformer import rotary_freqs, transformer_layer
from megatron_llm_tpu.ops.cross_entropy import vocab_parallel_cross_entropy
from megatron_llm_tpu.ops.layernorm import apply_norm
from megatron_llm_tpu.optimizer.optimizer import map_param_trees
from megatron_llm_tpu.parallel.layers import parallel_lm_logits

# ---------------------------------------------------------------------------
# VPP layer-stack layout
# ---------------------------------------------------------------------------

def vpp_stage_major_permutation(num_layers: int, pp: int, vpp: int):
    """Index array ``perm`` with ``stage_major = natural[perm]``.

    Stacked row ``j = k*(L/S) + v*cl + i`` holds natural layer
    ``(v*S + k)*cl + i`` so that a P('pp') sharding of the leading axis
    gives device k exactly its V interleaved chunks, in chunk order.
    """
    L, S, V = num_layers, pp, vpp
    assert L % (S * V) == 0, f"num_layers {L} must divide pp*vpp {S * V}"
    cl = L // (S * V)
    perm = np.empty(L, np.int64)
    j = 0
    for k in range(S):
        for v in range(V):
            for i in range(cl):
                perm[j] = (v * S + k) * cl + i
                j += 1
    return perm


def permute_layer_stack(layers, num_layers: int, pp: int, vpp: int):
    """Natural layer order -> stage-major order (no-op when vpp<=1)."""
    if vpp <= 1:
        return layers
    perm = vpp_stage_major_permutation(num_layers, pp, vpp)
    return jax.tree_util.tree_map(lambda x: x[perm], layers)


def unpermute_layer_stack(layers, num_layers: int, pp: int, vpp: int):
    """Stage-major order -> natural layer order (no-op when vpp<=1)."""
    if vpp <= 1:
        return layers
    perm = vpp_stage_major_permutation(num_layers, pp, vpp)
    inv = np.argsort(perm)
    return jax.tree_util.tree_map(lambda x: x[inv], layers)


def convert_params_layout(params, num_layers: int, pp: int, vpp: int,
                          *, to_stage_major: bool):
    """Permute the ``transformer.layers`` subtree of a params-like pytree
    between natural order (checkpoints, converters) and stage-major
    training order.  No-op when vpp<=1 or the subtree is absent."""
    if vpp <= 1 or params is None:
        return params
    tr = params.get("transformer") if isinstance(params, dict) else None
    if not isinstance(tr, dict) or "layers" not in tr:
        return params
    fn = permute_layer_stack if to_stage_major else unpermute_layer_stack
    out = dict(params)
    out["transformer"] = dict(tr)
    out["transformer"]["layers"] = fn(tr["layers"], num_layers, pp, vpp)
    return out


def convert_opt_state_layout(opt_state, num_layers: int, pp: int, vpp: int,
                             *, to_stage_major: bool):
    """Apply ``convert_params_layout`` to every params-shaped tree inside
    an ``OptimizerState`` (exp_avg / exp_avg_sq / master_params)."""
    if vpp <= 1:
        return opt_state
    return map_param_trees(
        lambda tree: convert_params_layout(
            tree, num_layers, pp, vpp, to_stage_major=to_stage_major),
        opt_state)


# ---------------------------------------------------------------------------
# Shared per-tick pieces
# ---------------------------------------------------------------------------

def _decode_item(w, M: int, S: int, V: int):
    """Work item w -> (microbatch m, chunk v, valid).  Mixed radix
    w = g*(S*V) + v*S + r with m = g*S + r; V==1 degenerates to m = w."""
    valid = (w >= 0) & (w < M * V)
    wc = jnp.clip(w, 0, M * V - 1)
    if V == 1:
        return wc, jnp.zeros_like(wc), valid
    g = wc // (S * V)
    rem = wc % (S * V)
    v = rem // S
    r = rem % S
    return g * S + r, v, valid


def _index_mb(arr, m):
    return lax.dynamic_index_in_dim(arr, m, 0, keepdims=False)


def _pipeline_embedding_layout(tree, mesh):
    """Replicate the small aux embedding tables (learned position /
    tokentype — their in-shard_map gathers need a replicated operand);
    the word table keeps its vocab(tp)-sharded layout.

    The word lookup inside the pp-manual shard_map goes through
    ``vocab_parallel_lookup_manual`` (masked local gather + tp-psum in a
    nested tp-manual region, the reference's VocabParallelEmbedding,
    ``layers.py:128-210``), so the GSPMD gather partitioner — which
    check-fails on a vocab-sharded operand under a manual submesh
    (spmd_partitioner_util.cc:495) — never sees it.  This replaces the
    round-2 workaround of all-gathering the full table per step
    (V*H replicated bytes per device: ~0.5 GB at 70B, plus a V*H fp32
    grad accumulator in the 1F1B carry)."""
    from jax.sharding import NamedSharding

    rep = NamedSharding(mesh, P())
    out = {
        k: jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, rep), v)
        for k, v in tree.items() if k != "word"
    }
    out["word"] = tree["word"]
    return out


def _fwd_rotation(S):
    return [(i, (i + 1) % S) for i in range(S)]


def _bwd_rotation(S):
    return [(i, (i - 1) % S) for i in range(S)]


# ---------------------------------------------------------------------------
# Engine 1: streaming autodiff schedule (supports VPP)
# ---------------------------------------------------------------------------

def build_pipeline_loss_fn(
    model,
    pp_size: int,
    num_microbatches: int,
    *,
    num_virtual: int = 1,
    sequence_parallel: bool = False,
    remat_block_ticks: Optional[int] = None,
):
    """Returns ``loss_fn(params, batch, rng_key, scale, train) ->
    (scaled_loss, loss)`` computing the full pipelined global-batch loss.
    For MoE configs (``num_experts > 1``) the return is
    ``(scaled_total, (loss, aux))`` where ``scaled_total`` includes the
    weighted routing losses and ``aux`` is the ``[lb, z]`` mean.

    ``batch``: dict with tokens/labels/loss_mask of shape [M, mb, s].
    ``params``: the standard model pytree; ``transformer.layers`` leaves
    (leading axis L) must be sharded over pp, in **stage-major order**
    when ``num_virtual > 1`` (see ``permute_layer_stack``).
    """
    cfg: TransformerConfig = model.cfg
    moe_on = cfg.num_experts > 1
    S, V, M, L = pp_size, num_virtual, num_microbatches, cfg.num_layers
    assert L % (S * V) == 0, f"num_layers {L} must divide pp*vpp {S * V}"
    if V > 1:
        # same constraint as the reference's interleaved schedule
        # (schedules.py:253-266: microbatches grouped by pipeline size)
        assert M % S == 0, (
            f"interleaved VPP requires num_microbatches ({M}) divisible by "
            f"pipeline size ({S})"
        )
    cl = L // (S * V)          # layers per chunk
    local_L = L // S           # layers per device
    W = M * V                  # work items
    T = W + S - 1              # fine ticks

    train_has_dropout = cfg.hidden_dropout > 0.0 or cfg.attention_dropout > 0.0

    def loss_fn(params, batch, rng_key, scale=1.0, train: bool = True):
        mesh = topology.get_mesh()
        emb_p = params["embedding"]
        trans = params["transformer"]
        head_w = lm_head_weight(params)
        freqs = rotary_freqs(cfg)
        tokens, labels, loss_mask = (
            batch["tokens"], batch["labels"], batch["loss_mask"],
        )
        mb, s = tokens.shape[1], tokens.shape[2]
        use_dropout = train and train_has_dropout

        def shmap_fn(layers_local, emb_p_, head_w_, fnorm_, tokens_,
                     labels_, mask_, rng_key_):
            pp_rank = lax.axis_index("pp")
            is_first = pp_rank == 0
            is_last = pp_rank == S - 1
            emb_key0 = jax.random.fold_in(rng_key_, 1)
            lay_key0 = jax.random.fold_in(rng_key_, 2)

            @jax.named_scope("pp_chunk")
            def run_chunk(h, v, m):
                """Apply this device's chunk v; returns (h, aux [2]) where
                aux is the chunk's accumulated MoE routing losses (zeros
                for dense models)."""
                def layer_body(carry, i):
                    hh, aux = carry
                    li = v * cl + i                       # local stacked row
                    lp = jax.tree_util.tree_map(
                        lambda x: lax.dynamic_index_in_dim(
                            x, li, 0, keepdims=False),
                        layers_local,
                    )
                    key = jax.random.fold_in(
                        jax.random.fold_in(lay_key0, m),
                        pp_rank * local_L + li,
                    )
                    out, _, a = transformer_layer(
                        hh, lp, cfg,
                        freqs=freqs, attention_mask=None, position_ids=None,
                        rng_key=key if use_dropout else None,
                        train=use_dropout,
                        sequence_parallel=sequence_parallel,
                    )
                    if moe_on:
                        aux = aux + a
                    return (out, aux), None

                (h, aux), _ = lax.scan(
                    layer_body, (h, jnp.zeros((2,), jnp.float32)),
                    jnp.arange(cl))
                return h, aux

            def tick(carry, t):
                act, ce_sum, tok_sum, aux_sum = carry
                w = t - pp_rank
                m, v, valid = _decode_item(w, M, S, V)
                toks_m = _index_mb(tokens_, m)
                h_emb = embedding_forward(
                    toks_m, None, emb_p_, cfg,
                    rng_key=(jax.random.fold_in(emb_key0, m)
                             if use_dropout else None),
                    train=use_dropout,
                    vocab_parallel_manual=True,
                ).astype(cfg.compute_jnp_dtype)
                inp = jnp.where(is_first & (v == 0), h_emb, act)
                out, aux_c = run_chunk(inp, v, m)
                # every stage owns cl layers of every valid item, so the
                # routing aux accrues on all stages (unlike CE)
                aux_sum = aux_sum + aux_c * valid.astype(jnp.float32)

                # streamed head + CE: valid only on (last stage, last chunk)
                h_fin = apply_norm(
                    out, fnorm_, cfg.normalization,
                    eps=cfg.layernorm_epsilon, fp32_compute=cfg.norm_in_fp32,
                )
                logits = parallel_lm_logits(
                    h_fin, head_w_,
                    sequence_parallel=False,
                    compute_dtype=cfg.compute_jnp_dtype,
                )
                ce = vocab_parallel_cross_entropy(
                    logits.astype(jnp.float32), _index_mb(labels_, m)
                )
                take = (is_last & (v == V - 1) & valid).astype(jnp.float32)
                wgt = _index_mb(mask_, m).astype(jnp.float32) * take
                act_next = lax.ppermute(out, "pp", _fwd_rotation(S))
                return (
                    act_next,
                    ce_sum + jnp.sum(ce * wgt),
                    tok_sum + jnp.sum(wgt),
                    aux_sum,
                ), None

            tick_fn = jax.checkpoint(
                tick, policy=jax.checkpoint_policies.nothing_saveable
            )

            # blocked outer scan: backward stores T/B block-carries and
            # recomputes B tick-carries per block -> O(sqrt(T)) live carries
            B = remat_block_ticks or max(1, int(np.ceil(np.sqrt(T))))
            n_blocks = -(-T // B)

            def block(carry, b):
                return lax.scan(tick_fn, carry, b * B + jnp.arange(B))

            block_fn = jax.checkpoint(
                block, policy=jax.checkpoint_policies.nothing_saveable
            )
            act0 = jnp.zeros((mb, s, cfg.hidden_size), cfg.compute_jnp_dtype)
            (act_f, ce_sum, tok_sum, aux_sum), _ = lax.scan(
                block_fn,
                (act0, jnp.float32(0.0), jnp.float32(0.0),
                 jnp.zeros((2,), jnp.float32)),
                jnp.arange(n_blocks),
            )
            # ticks beyond T (block padding) decode to invalid items -> masked
            ce_tot = lax.psum(ce_sum, "pp")
            tok_tot = lax.psum(tok_sum, "pp")
            aux_tot = lax.psum(aux_sum, "pp")
            return ce_tot, tok_tot, aux_tot

        layer_in_spec = jax.tree_util.tree_map(lambda _: P("pp"),
                                               trans["layers"])
        rep = jax.tree_util.tree_map(lambda _: P(), emb_p)
        fnorm_spec = jax.tree_util.tree_map(lambda _: P(),
                                            trans["final_norm"])
        ce_tot, tok_tot, aux_tot = jax.shard_map(
            shmap_fn,
            mesh=mesh,
            in_specs=(layer_in_spec, rep, P(), fnorm_spec, P(), P(), P(), P()),
            out_specs=(P(), P(), P()),
            axis_names={"pp"},
            check_vma=False,
        )(trans["layers"], _pipeline_embedding_layout(emb_p, mesh), head_w,
          trans["final_norm"], tokens, labels, loss_mask, rng_key)

        loss = ce_tot / jnp.maximum(tok_tot, 1.0)
        if moe_on:
            # mean routing aux per microbatch enters the objective with the
            # configured coefficients; (loss, aux) is reported for logging
            aux_mean = aux_tot / M
            total = (loss + cfg.moe_aux_loss_coeff * aux_mean[0]
                     + cfg.moe_z_loss_coeff * aux_mean[1])
            return total * scale, (loss, aux_mean)
        return loss * scale, loss

    return loss_fn


# ---------------------------------------------------------------------------
# Engine 2: manual 1F1B with O(S) activation stash (V=1)
# ---------------------------------------------------------------------------

def build_pipeline_grad_fn(
    model,
    pp_size: int,
    num_microbatches: int,
    *,
    sequence_parallel: bool = False,
):
    """Returns ``grad_fn(params, batch, rng_key, scale, train) ->
    (loss, grads)`` with a hand-scheduled 1F1B backward; for MoE configs
    (``num_experts > 1``) it returns ``(loss, grads, aux)`` with the
    ``[lb, z]`` routing-aux mean, and ``grads`` are gradients of the full
    weighted objective.

    Activation memory is flat in M: the scan is never autodiffed, so the
    only live state is the carry — one fwd activation, one bwd cotangent,
    a 2S-slot input stash (the reference's in-flight cap,
    schedules.py:606-722), and fp32 gradient accumulators.  ``grads`` are
    gradients of ``scale * mean CE`` in fp32, matching
    ``jax.grad(loss_fn)`` of the streaming engine.
    """
    cfg: TransformerConfig = model.cfg
    moe_on = cfg.num_experts > 1
    S, M, L = pp_size, num_microbatches, cfg.num_layers
    assert L % S == 0, f"num_layers {L} must divide pp {S}"
    cl = L // S
    R = 2 * S                     # stash ring slots (max residence 2S-1)
    T = M + 2 * S - 1             # fwd item f = t - k; bwd item b = t - (2S-1-k)

    train_has_dropout = cfg.hidden_dropout > 0.0 or cfg.attention_dropout > 0.0

    def grad_fn(params, batch, rng_key, scale=1.0, train: bool = True):
        mesh = topology.get_mesh()
        emb_p = params["embedding"]
        trans = params["transformer"]
        untied = "lm_head" in params
        head_w = lm_head_weight(params)
        freqs = rotary_freqs(cfg)
        tokens, labels, loss_mask = (
            batch["tokens"], batch["labels"], batch["loss_mask"],
        )
        mb, s = tokens.shape[1], tokens.shape[2]
        use_dropout = train and train_has_dropout
        # total token count is known before the pipeline runs; each item's
        # cotangent seed folds in the 1/total normalization
        tok_tot = jnp.maximum(jnp.sum(loss_mask.astype(jnp.float32)), 1.0)

        def shmap_fn(layers_local, emb_p_, head_w_, fnorm_, tokens_,
                     labels_, mask_, rng_key_, seed_, aux_seed_):
            pp_rank = lax.axis_index("pp")
            is_first = (pp_rank == 0).astype(jnp.float32)
            is_last = (pp_rank == S - 1).astype(jnp.float32)
            emb_key0 = jax.random.fold_in(rng_key_, 1)
            lay_key0 = jax.random.fold_in(rng_key_, 2)

            @jax.named_scope("pp_chunk_fwd")
            def chunk_fwd(h, layers_loc, m):
                """(h, aux [2]): this stage's cl layers + its MoE routing
                losses (zeros for dense models)."""
                def layer_body(carry, i):
                    hh, aux = carry
                    lp = jax.tree_util.tree_map(
                        lambda x: lax.dynamic_index_in_dim(
                            x, i, 0, keepdims=False),
                        layers_loc,
                    )
                    key = jax.random.fold_in(
                        jax.random.fold_in(lay_key0, m), pp_rank * cl + i
                    )
                    out, _, a = transformer_layer(
                        hh, lp, cfg,
                        freqs=freqs, attention_mask=None, position_ids=None,
                        rng_key=key if use_dropout else None,
                        train=use_dropout,
                        sequence_parallel=sequence_parallel,
                    )
                    if moe_on:
                        aux = aux + a
                    return (out, aux), None

                (h, aux), _ = lax.scan(
                    layer_body, (h, jnp.zeros((2,), jnp.float32)),
                    jnp.arange(cl))
                return h, aux

            @jax.named_scope("pp_embed")
            def embed(emb_params, m):
                toks_m = _index_mb(tokens_, m)
                return embedding_forward(
                    toks_m, None, emb_params, cfg,
                    rng_key=(jax.random.fold_in(emb_key0, m)
                             if use_dropout else None),
                    train=use_dropout,
                    vocab_parallel_manual=True,
                ).astype(cfg.compute_jnp_dtype)

            @jax.named_scope("pp_head_ce")
            def head_ce(out, head_w_in, fnorm_in, m):
                h_fin = apply_norm(
                    out, fnorm_in, cfg.normalization,
                    eps=cfg.layernorm_epsilon, fp32_compute=cfg.norm_in_fp32,
                )
                logits = parallel_lm_logits(
                    h_fin, head_w_in,
                    sequence_parallel=False,
                    compute_dtype=cfg.compute_jnp_dtype,
                )
                ce = vocab_parallel_cross_entropy(
                    logits.astype(jnp.float32), _index_mb(labels_, m)
                )
                wgt = _index_mb(mask_, m).astype(jnp.float32)
                return jnp.sum(ce * wgt), jnp.sum(wgt)

            def tick(carry, t):
                act_f, act_b, stash, g_lay, g_emb, g_head, g_norm, \
                    ce_sum, tok_sum, aux_sum = carry

                # ---------------- forward chunk ---------------------------
                f = t - pp_rank
                m_f, _, valid_f = _decode_item(f, M, S, 1)
                h_emb = embed(emb_p_, m_f)
                inp = jnp.where((pp_rank == 0), h_emb, act_f)
                out, _ = chunk_fwd(inp, layers_local, m_f)
                # stash the chunk input for the backward recompute
                slot_f = jnp.mod(f, R)
                old = lax.dynamic_index_in_dim(stash, slot_f, 0,
                                               keepdims=False)
                stash = lax.dynamic_update_index_in_dim(
                    stash,
                    jnp.where(valid_f, inp, old),
                    slot_f, 0,
                )
                act_f_next = lax.ppermute(out, "pp", _fwd_rotation(S))

                # ---------------- backward chunk --------------------------
                b = t - (2 * S - 1 - pp_rank)
                m_b, _, valid_b = _decode_item(b, M, S, 1)
                vmask = valid_b.astype(jnp.float32)
                slot_b = jnp.mod(b, R)
                x = lax.dynamic_index_in_dim(stash, slot_b, 0, keepdims=False)

                def fwd_path(x_in, layers_loc, head_in, fnorm_in):
                    o, aux_c = chunk_fwd(x_in, layers_loc, m_b)
                    ce, wgt = head_ce(o, head_in, fnorm_in, m_b)
                    return o, ce, wgt, aux_c

                (o_b, ce_b, wgt_b, aux_b), vjp = jax.vjp(
                    fwd_path, x, layers_local, head_w_, fnorm_
                )
                # last stage seeds from CE; other stages from the incoming
                # cotangent (zeroed on the last stage).  The routing aux is
                # seeded on EVERY stage (each owns its layers' routers).
                cot_o = (act_b * (1.0 - is_last)).astype(o_b.dtype)
                cot_ce = (seed_ * is_last * vmask).astype(ce_b.dtype)
                cot_aux = aux_seed_ * vmask
                dx, d_lay, d_head, d_norm = vjp(
                    (cot_o, cot_ce, jnp.zeros_like(wgt_b), cot_aux)
                )
                # first stage: push dx through the embedding lookup
                _, emb_vjp = jax.vjp(lambda ep: embed(ep, m_b), emb_p_)
                (d_emb,) = emb_vjp(
                    (dx * is_first * vmask).astype(cfg.compute_jnp_dtype)
                )

                g_lay = jax.tree_util.tree_map(
                    lambda g, d: g + d.astype(jnp.float32) * vmask,
                    g_lay, d_lay)
                g_emb = jax.tree_util.tree_map(
                    lambda g, d: g + d.astype(jnp.float32), g_emb, d_emb)
                g_head = g_head + d_head.astype(jnp.float32) * (is_last * vmask)
                g_norm = jax.tree_util.tree_map(
                    lambda g, d: g + d.astype(jnp.float32) * (is_last * vmask),
                    g_norm, d_norm)
                ce_sum = ce_sum + ce_b * is_last * vmask
                tok_sum = tok_sum + wgt_b * is_last * vmask
                aux_sum = aux_sum + aux_b * vmask

                act_b_next = lax.ppermute(
                    (dx * vmask).astype(cfg.compute_jnp_dtype),
                    "pp", _bwd_rotation(S),
                )
                return (act_f_next, act_b_next, stash, g_lay, g_emb,
                        g_head, g_norm, ce_sum, tok_sum, aux_sum), None

            zeros_f32 = lambda tree: jax.tree_util.tree_map(  # noqa: E731
                lambda x: jnp.zeros(x.shape, jnp.float32), tree)
            act0 = jnp.zeros((mb, s, cfg.hidden_size), cfg.compute_jnp_dtype)
            carry0 = (
                act0,
                act0,
                jnp.zeros((R, mb, s, cfg.hidden_size), cfg.compute_jnp_dtype),
                zeros_f32(layers_local),
                zeros_f32(emb_p_),
                jnp.zeros(head_w_.shape, jnp.float32),
                zeros_f32(fnorm_),
                jnp.float32(0.0),
                jnp.float32(0.0),
                jnp.zeros((2,), jnp.float32),
            )
            carry, _ = lax.scan(tick, carry0, jnp.arange(T))
            (_, _, _, g_lay, g_emb, g_head, g_norm,
             ce_sum, tok_sum, aux_sum) = carry
            # replicated-param grads: emit per-stage contributions stacked
            # over pp and sum them outside the shard_map — an in-body psum
            # of a tp-auto-sharded array over the manual pp axis trips the
            # same partitioner check as the vocab-sharded gather
            stack = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda g: g[None], t)
            ce_tot = lax.psum(ce_sum, "pp")
            tok_tot_ = lax.psum(tok_sum, "pp")
            aux_tot = lax.psum(aux_sum, "pp")
            return (g_lay, stack(g_emb), g_head[None], stack(g_norm),
                    ce_tot, tok_tot_, aux_tot)

        layer_in_spec = jax.tree_util.tree_map(lambda _: P("pp"),
                                               trans["layers"])
        rep_emb = jax.tree_util.tree_map(lambda _: P(), emb_p)
        fnorm_spec = jax.tree_util.tree_map(lambda _: P(),
                                            trans["final_norm"])
        stacked_emb = jax.tree_util.tree_map(lambda _: P("pp"), emb_p)
        stacked_fnorm = jax.tree_util.tree_map(lambda _: P("pp"),
                                               trans["final_norm"])
        # cotangent seed: d(scale * mean CE)/d(per-item CE sum)
        seed = jnp.float32(scale) / tok_tot
        # routing-aux cotangent: d(scale * coeff . mean-per-microbatch aux)
        aux_seed = (jnp.float32(scale) / M) * jnp.asarray(
            [cfg.moe_aux_loss_coeff, cfg.moe_z_loss_coeff], jnp.float32)
        g_lay, g_emb, g_head, g_norm, ce_tot, tok_tot_, aux_tot = jax.shard_map(
            shmap_fn,
            mesh=mesh,
            in_specs=(layer_in_spec, rep_emb, P(), fnorm_spec,
                      P(), P(), P(), P(), P(), P()),
            out_specs=(layer_in_spec, stacked_emb, P("pp"), stacked_fnorm,
                       P(), P(), P()),
            axis_names={"pp"},
            check_vma=False,
        )(trans["layers"], _pipeline_embedding_layout(emb_p, mesh), head_w,
          trans["final_norm"], tokens, labels, loss_mask, rng_key, seed,
          aux_seed)
        sum_pp = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda g: jnp.sum(g, axis=0), t)
        g_emb = sum_pp(g_emb)
        g_head = jnp.sum(g_head, axis=0)
        g_norm = sum_pp(g_norm)

        loss = ce_tot / jnp.maximum(tok_tot_, 1.0)
        grads = {
            "embedding": g_emb,
            "transformer": {"layers": g_lay, "final_norm": g_norm},
        }
        if untied:
            grads["lm_head"] = {"weight": g_head}
        else:
            grads["embedding"]["word"]["embedding"] = (
                grads["embedding"]["word"]["embedding"] + g_head
            )
        if moe_on:
            return loss, grads, aux_tot / M
        return loss, grads

    return grad_fn


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def build_pipeline_train_step(
    model,
    optimizer,
    parallel_cfg,
    num_microbatches: int,
    *,
    schedule: Optional[str] = None,
    layer_stats: bool = False,
):
    """Pipelined analogue of ``training.build_train_step``: full global batch
    through the pipeline, then the functional optimizer step.

    ``schedule``: '1f1b' (manual backward, O(S) activation stash; V=1 only)
    or 'stream' (autodiff engine, supports VPP).  Default: 1f1b when
    vpp==1, stream otherwise.

    ``layer_stats`` threads the model-health observatory (``health.py``)
    through both schedules: the grads the pipeline grad fn returns are a
    full (pp-sharded) param-tree pytree at the top level of the jitted
    step, so the per-group reductions run under GSPMD exactly like the
    single-program path and ``metrics['layer_stats']`` matches it.  NB
    with interleaved VPP the stacked-layer rows are stage-major, so the
    ``layer_NNN`` group names index stacked rows, not execution order.
    """
    pp = parallel_cfg.pipeline_model_parallel_size
    vpp = parallel_cfg.virtual_pipeline_model_parallel_size or 1
    if schedule is None:
        schedule = "1f1b" if vpp == 1 else "stream"
    if schedule == "1f1b" and vpp > 1:
        raise ValueError("manual 1f1b schedule supports vpp=1 only; "
                         "use schedule='stream' for interleaved VPP")

    moe_on = model.cfg.num_experts > 1

    def moe_metrics(metrics, aux):
        metrics["moe aux loss"] = aux[0]
        if model.cfg.moe_z_loss_coeff > 0.0:
            metrics["moe z loss"] = aux[1]

    if schedule == "1f1b":
        grad_fn = build_pipeline_grad_fn(
            model, pp, num_microbatches,
            sequence_parallel=parallel_cfg.sequence_parallel,
        )

        def train_step(params, opt_state, batch, rng_key, lr, wd):
            scale = opt_state.grad_scaler.scale
            out = grad_fn(params, batch, rng_key, scale)
            loss, grads = out[0], out[1]
            new_params, new_opt_state, stats = optimizer.step(
                params, grads, opt_state, lr, wd, layer_stats=layer_stats
            )
            metrics = {
                "lm loss": loss,
                "grad_norm": stats["grad_norm"],
                "loss_scale": stats["loss_scale"],
                "skipped_iter": stats["found_inf"].astype(jnp.int32),
            }
            if layer_stats:
                metrics["layer_stats"] = stats["layer_stats"]
            if moe_on:
                moe_metrics(metrics, out[2])
            return new_params, new_opt_state, metrics

        return jax.jit(train_step, donate_argnums=(0, 1))

    loss_fn = build_pipeline_loss_fn(
        model, pp, num_microbatches,
        num_virtual=vpp,
        sequence_parallel=parallel_cfg.sequence_parallel,
    )

    def train_step(params, opt_state, batch, rng_key, lr, wd):
        scale = opt_state.grad_scaler.scale

        def scaled_loss(p):
            return loss_fn(p, batch, rng_key, scale)

        (_, lfaux), grads = jax.value_and_grad(scaled_loss, has_aux=True)(params)
        loss, moe_aux = lfaux if moe_on else (lfaux, None)
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        new_params, new_opt_state, stats = optimizer.step(
            params, grads, opt_state, lr, wd, layer_stats=layer_stats
        )
        metrics = {
            "lm loss": loss,
            "grad_norm": stats["grad_norm"],
            "loss_scale": stats["loss_scale"],
            "skipped_iter": stats["found_inf"].astype(jnp.int32),
        }
        if layer_stats:
            metrics["layer_stats"] = stats["layer_stats"]
        if moe_on:
            moe_metrics(metrics, moe_aux)
        return new_params, new_opt_state, metrics

    return jax.jit(train_step, donate_argnums=(0, 1))
