"""Mixture-of-experts MLP: a dropless path for inference, a capacity
einsum for training (TPU-native extension).

The reference has no MoE (SURVEY §2.2: expert parallelism "absent"); this
module goes beyond parity.  Two paths; ``models/transformer.py`` takes one
or the other by its ``train`` argument and nothing else (no flag):

* **Dropless, whenever the model runs for inference**
  (``moe_mlp_dropless``, ``train=False``: every engine program — prefill
  chunk, decode step, verify step — and the plain forward).  The step's tokens are flattened, the ``tokens x k``
  assignments are sorted by expert, the expert matrices run as grouped
  matrix multiplications over the sorted rows (the Pallas kernel of
  ``ops/pallas/grouped_matmul.py`` on a TPU, ``jax.lax.ragged_dot``
  elsewhere) and the results are gathered back, once and in their own
  dtype, and summed under the gates in fp32 (scope ``moe_combine``).  No
  token is dropped at any routing; a token that is not live (a slot
  that is not decoding, the padding of a short chunk: ``live`` false) is
  sent to no expert; the groups of experts nobody chose are empty.  The
  published sparse models (Mixtral, OLMoE) are dropless, so this is what
  agrees with their references.  It also returns the histogram of live
  assignments ``[E]`` that the serving engine's routing counters read.
* **Capacity einsum, for training** (``moe_mlp``, ``train=True``): the
  GShard/Switch dispatch formulation as adapted by the public TPU stacks
  (t5x/flaxformer, MaxText).  Routing and dispatch are pure einsums over
  one-hot masks, so GSPMD can pattern-match the token->expert reshuffle
  into all-to-alls over ICI instead of host gathers.  Tokens route
  within their batch row ([b, s, h] -> groups of s tokens) with a
  per-group capacity ``c = max(min_capacity, ceil(s * top_k / E *
  capacity_factor))`` — bounds the dispatch mask at [b, s*k, E, c]
  instead of the unmanageable global [N, E, C].  Tokens over capacity are
  dropped (their MLP contribution is zero and the residual stream carries
  them unchanged) — standard capacity-style MoE semantics.  It stays
  until experts train over several chips on the dropless path too
  (ROADMAP R1, second half), and as a reference in the tests.

Shared by both:

* **The router** in fp32: softmax over all experts, the ``top_k``
  largest.  With ``cfg.norm_topk_prob`` (Mixtral: the softmax over the
  chosen experts) the chosen gates are renormalised to sum to 1; without
  it (OLMoE) they are used as the softmax gives them.  Its other forms
  are data of the config (``_route``): ``moe_score_function='sigmoid'``
  scores each expert by itself; with ``moe_choice_bias`` the ``top_k``
  are chosen over score + a bias an expert (a buffer of the param tree,
  ``router.choice_bias``) while the gates stay the scores;
  ``moe_routed_scale`` multiplies the (renormalised) gates.
* **The shared MLP** (``cfg.moe_shared_experts`` > 0): one MLP of that
  many experts' width (gated under a GLU like any other: built at
  ``mult * wide``) that every token passes through, NOT WEIGHTED BY THE
  ROUTER, added to the routed sum under the scope ``moe_shared``; with
  ``cfg.moe_shared_expert_gate`` times ``sigmoid(x w_s)``, one gate a
  token of its own (qwen3_next's ``shared_expert_gate``).
* **One chip's share of a layer's experts**
  (``cfg.moe_router_experts`` / ``cfg.moe_experts_first``; inference
  only): the router scores ALL the layer's experts and the layer holds
  ``cfg.num_experts`` contiguous ones of them.  What is routed: every
  token over all the router's outputs, gates normalised over all its
  ``top_k`` choices.  What is computed: the choices that fall on a held
  expert, under those gates.  What is dropped: the others, which the
  chip that holds them computes; summed over the chips' shares, with the
  shared MLP once, that is the uncut layer
  (``tests/test_moe.py::test_the_halves_sum_to_the_whole``).  The
  histogram stays the ROUTER's, over all its outputs.
* **Expert placement**: expert-stacked weights ``[E, ...]`` carry the
  ``'expert'`` logical axis, which the sharding rules map onto the ``dp``
  mesh axis (EP folded into dp, ``parallel/sharding.py``); the per-expert
  FFN dims keep the usual ``'ffn'`` -> tp sharding, so one expert's GEMMs
  are tensor-parallel exactly like the dense MLP's.
* **Load balance**: Switch-style aux loss ``E * sum_e(frac_e * prob_e)``
  plus router z-loss, returned unweighted as a ``[lb, z]`` fp32 vector;
  the trainer adds ``moe_aux_loss_coeff * lb + moe_z_loss_coeff * z``.
* **Composes with the pipeline engines** (``parallel/pipeline.py``): the
  ``[lb, z]`` aux rides the tick carry of both schedules and the manual
  1F1B backward seeds its cotangent on every stage, so tp x pp x dp(=ep)
  x sp train together (parity-tested in ``tests/test_pipeline.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import TransformerConfig
from megatron_llm_tpu.ops.activations import apply_mlp_activation
from megatron_llm_tpu.parallel.layers import (
    column_parallel_linear,
    init_linear_params,
    init_method_for,
    row_parallel_linear,
    scaled_init_method_normal,
)
from megatron_llm_tpu.parallel.sharding import constrain
from megatron_llm_tpu.quantization import dequantize_weight


def moe_capacity(cfg: TransformerConfig, seq_len: int) -> int:
    """Per-(batch-row, expert) token buffer size — static at trace time."""
    c = math.ceil(seq_len * cfg.moe_top_k / cfg.num_experts
                  * cfg.moe_capacity_factor)
    return max(cfg.moe_min_capacity, c)


def expert_axis(num_experts: int):
    """``'expert'`` when the expert dim can shard over dp (E % dp == 0 on
    an initialized mesh), else ``None`` (replicated experts — correct, just
    not expert-parallel; covers tiny-E tests and E < dp meshes).

    Reads *global* topology state — callers on the model path must resolve
    this once (``resolve_expert_axis``) and carry the answer in
    ``cfg.moe_expert_axis`` so param placement (spec time) and activation
    constraints (trace time) cannot diverge if the mesh is re-initialized
    in between (round-3 advisor finding)."""
    from megatron_llm_tpu import topology

    try:
        dp = topology.get_data_parallel_world_size()
    except RuntimeError:
        return None
    return "expert" if num_experts % dp == 0 else None


def resolve_expert_axis(cfg: TransformerConfig) -> TransformerConfig:
    """Pin ``moe_expert_axis='auto'`` to the current mesh's answer; no-op
    for dense configs or already-resolved ones.  With NO mesh initialized
    yet the config stays ``'auto'`` (later live derivation) — pinning
    'replicated' here would permanently disable expert parallelism for a
    model constructed before ``initialize_model_parallel``."""
    if cfg.num_experts > 1 and cfg.moe_expert_axis == "auto":
        from megatron_llm_tpu import topology

        try:
            dp = topology.get_data_parallel_world_size()
        except RuntimeError:
            return cfg
        return cfg.replace(
            moe_expert_axis="expert" if cfg.num_experts % dp == 0
            else "replicated")
    return cfg


def _cfg_expert_axis(cfg: TransformerConfig):
    """Resolved logical axis for the expert dim: ``'expert'`` or ``None``.
    Falls back to live derivation only for unresolved (``'auto'``) configs
    — direct unit-test calls that never went through a model wrapper."""
    if cfg.moe_expert_axis == "auto":
        return expert_axis(cfg.num_experts)
    return "expert" if cfg.moe_expert_axis == "expert" else None


# the spread a fresh model's choice bias is drawn at, unless the config
# gives another (``moe_choice_bias_std``).  Published biases are buffers
# moved by load balancing, and a checkpoint overwrites this; drawn at
# zero, leaving the bias out of the choice would be a fault no test or
# probe could see
_CHOICE_BIAS_STD = 0.1
_LANES = 128


def laid_width(cfg: TransformerConfig) -> int:
    """The columns ``experts['w_in']`` is LAID OUT at: an expert's width,
    or, for an ungated expert whose width is over one row of lanes and no
    multiple of it (1856 = 14.5 x 128), the next multiple, the columns
    past the width zeros.  A layout and no width of the model: what
    those columns give is dropped before ``w_out``, so the mathematics
    is that of the width.  Why: an array ``[.., H, F]`` whose ``F`` is
    not whole lanes and whose ``H`` is lies on the TPU with ``H`` minor,
    and the program then copies ALL the experts into the kernel's layout
    at every launch (3.83 GB a decode step at 6 layers x 64 experts of
    2688 x 1856, 13.9 ms a layer where the laid-out width takes 1.81:
    PERF.md section 6, PR 44).  A GLU's doubled first projection is
    split at its middle and keeps its width."""
    F = cfg.expert_hidden_size
    if cfg.glu_activation or F <= _LANES or F % _LANES == 0:
        return F
    return -(-F // _LANES) * _LANES


def init_moe_mlp_params(key, cfg: TransformerConfig, dtype):
    """{'router': {'kernel': [H, E][, 'choice_bias': [E]]},
        'experts': {'w_in': [E, H, (2x)F], 'w_out': [E, F, H]}
        [, 'shared': a dense MLP's two linears at moe_shared_experts * F
           (, 'gate': {'kernel': [H, 1]} with moe_shared_expert_gate)]}
    (``w_in``'s last dimension is ``laid_width``: ``F`` but for an
    ungated width that is not whole lanes)."""
    k_r, k_in, k_out = jax.random.split(key, 3)
    init = init_method_for(cfg)
    out_init = (
        scaled_init_method_normal(cfg.init_method_std, cfg.num_layers)
        if cfg.use_scaled_init_method
        else init
    )
    E, H, F = cfg.num_experts, cfg.hidden_size, cfg.expert_hidden_size
    mult = 2 if cfg.glu_activation else 1
    params = {
        # the router scores every expert of the layer, held here or not
        "router": {"kernel": init(k_r, (H, cfg.routed_experts), dtype)},
        "experts": {
            # zeros past the width where it is laid out wider (a GLU's
            # ``laid_width`` is its own: nothing is added)
            "w_in": jnp.pad(init(k_in, (E, H, mult * F), dtype),
                            ((0, 0), (0, 0), (0, laid_width(cfg) - F))),
            "w_out": out_init(k_out, (E, F, H), dtype),
        },
    }
    if cfg.moe_choice_bias:
        params["router"]["choice_bias"] = (
            (_CHOICE_BIAS_STD if cfg.moe_choice_bias_std is None
             else cfg.moe_choice_bias_std) * jax.random.normal(
                jax.random.fold_in(k_r, 1), (cfg.routed_experts,),
                jnp.float32)).astype(dtype)
    if cfg.moe_shared_experts:
        k_si, k_so = jax.random.split(jax.random.fold_in(key, 1))
        wide = cfg.moe_shared_experts * F
        params["shared"] = {
            "dense_h_to_4h": init_linear_params(
                k_si, H, mult * wide, bias=False, init_method=init,
                dtype=dtype),
            "dense_4h_to_h": init_linear_params(
                k_so, wide, H, bias=False, init_method=out_init,
                dtype=dtype),
        }
        if cfg.moe_shared_expert_gate:
            params["shared"]["gate"] = init_linear_params(
                jax.random.fold_in(key, 2), H, 1, bias=False,
                init_method=init, dtype=dtype)
    return params


def moe_mlp_specs(params, stacked: bool = True, cfg=None) -> dict:
    lead = ("stage",) if stacked else ()
    E = params["experts"]["w_in"].shape[1 if stacked else 0]
    ex = _cfg_expert_axis(cfg) if cfg is not None else expert_axis(E)
    specs = {
        "router": {"kernel": lead + (None, None)},
        "experts": {
            "w_in": lead + (ex, None, "ffn"),
            "w_out": lead + (ex, "ffn", None),
        },
    }
    if "choice_bias" in params["router"]:
        specs["router"]["choice_bias"] = lead + (None,)
    if "shared" in params:
        specs["shared"] = {
            "dense_h_to_4h": {"kernel": lead + (None, "ffn")},
            "dense_4h_to_h": {"kernel": lead + ("ffn", None)},
        }
        if "gate" in params["shared"]:
            specs["shared"]["gate"] = {"kernel": lead + (None, None)}
    return specs


def _route(x: jax.Array, params, cfg: TransformerConfig):
    """x [..., h] -> (logits, probs [..., E], gates, idx [..., k]): the
    router in fp32.  The scores are the softmax over all experts or a
    sigmoid an expert (``cfg.moe_score_function``); the ``top_k`` are the
    largest scores, or with ``router.choice_bias`` the largest of score +
    bias, and the gates are the chosen SCORES either way, renormalised
    over the chosen ones only where the family does
    (``cfg.norm_topk_prob``, the division guarded as
    ``cfg.moe_gate_norm_eps`` / ``cfg.moe_gate_norm_added`` say) and then
    times ``cfg.moe_routed_scale``."""
    wr = params["router"]["kernel"].astype(jnp.float32)
    logits = jnp.einsum("...h,he->...e", x.astype(jnp.float32), wr)
    if cfg.moe_score_function == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    if cfg.moe_choice_bias:
        _, idx = jax.lax.top_k(
            probs + params["router"]["choice_bias"].astype(jnp.float32),
            cfg.moe_top_k)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
    else:
        gates, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.norm_topk_prob:
        # the division's guard is the family's, as data: the sum plus an
        # epsilon, or the larger of the two
        total = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / (total + cfg.moe_gate_norm_eps
                         if cfg.moe_gate_norm_added
                         else jnp.maximum(total, cfg.moe_gate_norm_eps))
    if cfg.moe_routed_scale != 1.0:
        gates = gates * cfg.moe_routed_scale
    return logits, probs, gates, idx


def _shared_mlp(x: jax.Array, params, cfg: TransformerConfig):
    """The shared experts' MLP on x [b, s, h] (zeros' stand-in None for a
    model without one): every token, not weighted by the router; with
    ``cfg.moe_shared_expert_gate`` under its OWN gate, ``sigmoid(x
    w_s)`` a token in float32 (then the output is float32)."""
    if "shared" not in params:
        return None
    with jax.named_scope("moe_shared"):
        mid = column_parallel_linear(
            x, params["shared"]["dense_h_to_4h"], out_logical="ffn",
            compute_dtype=cfg.compute_jnp_dtype)
        out = row_parallel_linear(
            apply_mlp_activation(mid, cfg), params["shared"]["dense_4h_to_h"],
            in_logical="ffn", compute_dtype=cfg.compute_jnp_dtype)
        if cfg.moe_shared_expert_gate:
            w_s = params["shared"]["gate"]["kernel"].astype(jnp.float32)
            out = out.astype(jnp.float32) * jax.nn.sigmoid(
                x.astype(jnp.float32) @ w_s)
        return out


def _aux_losses(logits, probs, frac):
    """Unweighted [load-balance, z] (fp32) — the trainer applies
    moe_aux_loss_coeff / moe_z_loss_coeff.  Switch load balance:
    E * sum_e(assignment-fraction_e * mean-prob_e); == 1 at a perfectly
    uniform router."""
    E = probs.shape[-1]
    mean_prob = jnp.mean(probs.reshape(-1, E), axis=0)
    lb = E * jnp.sum(frac * mean_prob)
    z = jax.nn.logsumexp(logits, axis=-1)
    return jnp.stack([lb, jnp.mean(z * z)])


def _grouped_matmul(rows: jax.Array, weights: jax.Array,
                    group_sizes: jax.Array) -> jax.Array:
    """rows [m, k] sorted by group, weights [G, k, n], group_sizes [G] ->
    [m, n]: group g's rows times weights[g] (products in the rows'
    dtype, fp32 accumulation).  Rows past ``sum(group_sizes)`` belong to
    no group and what comes back for them is not used.

    On one TPU device the Pallas kernel (``ops/pallas/grouped_matmul.py``
    says why); elsewhere — the CPU, or a mesh of several devices, across
    which a Mosaic call cannot be partitioned — XLA's ragged dot."""
    from megatron_llm_tpu import topology
    from megatron_llm_tpu.ops.pallas import grouped_matmul as gm

    one_device = (not topology.model_parallel_is_initialized()
                  or topology.get_mesh().size == 1)
    if gm.kernel_available() and one_device:
        return gm.grouped_matmul(rows, weights, group_sizes)
    return jax.lax.ragged_dot(rows, weights, group_sizes)


def _layer_of_stacked(experts, layer: int, counts: jax.Array, cdtype):
    """(experts, group sizes) of layer ``layer`` of a model's stacked
    experts ``[L, E, ...]``.

    A grouped matmul is a kernel call and wants its operand whole:
    handed ``stacked[layer]`` it would first copy the layer's every
    expert (805 MB a layer at OLMoE's widths).  So weights that are
    already the matmul's operand (the compute dtype) stay stacked,
    reshaped to ``[L * E, ...]`` (the same bytes, no copy), and the group
    sizes are padded so that only this layer's groups are not empty.
    Weights that are dequantized first are a new array either way: they
    are sliced."""
    if "w_in" in experts and experts["w_in"].dtype == cdtype:
        L, E = experts["w_in"].shape[:2]
        merged = {k: w.reshape((L * E,) + w.shape[2:])
                  for k, w in experts.items()}
        return merged, jnp.pad(counts, (layer * E, (L - 1 - layer) * E))
    return jax.tree_util.tree_map(lambda w: w[layer], experts), counts


def moe_mlp_dropless(x: jax.Array, params, cfg: TransformerConfig,
                     live: jax.Array = None, layer: int = None):
    """x [b, s, h] -> (out [b, s, h], aux [2] fp32, counts [E] int32).

    ``live`` [b, s] bool (None: every token) marks the tokens of the step
    that are real; the others are routed nowhere, add nothing to
    ``counts`` and get a zero output.  ``counts[e]`` is the number of
    live (token, choice) assignments expert e received.

    Where the layer holds a SHARE of the experts its router scores
    (``cfg.holds_a_share``), ``counts`` is still the router's histogram,
    over all ``cfg.routed_experts``; an assignment whose expert is not
    held goes where a dead token's goes (index ``E``, behind every real
    group, gate 0), so the sort, the grouped matmuls and the gather run
    over the held groups alone, under the gates the router gave over all
    the token's choices.

    With ``layer`` (a static index: the paged-cache loop of
    ``transformer_stack``, so every engine program) ``params['experts']``
    holds the weights of EVERY layer, stacked ``[L, E, ...]`` as the model
    keeps them, and this is layer ``layer`` of them (``_layer_of_stacked``
    decides how it is taken).
    """
    E, k = cfg.num_experts, cfg.moe_top_k
    R = cfg.routed_experts          # E, unless the layer holds a share
    b, s, h = x.shape
    T = b * s
    cdtype = cfg.compute_jnp_dtype
    xf = x.reshape(T, h)

    with jax.named_scope("moe_route"):
        logits, probs, gates, idx = _route(xf, params, cfg)    # [T, k]
        if live is not None:
            alive = live.reshape(T, 1)
            # R is no expert: it sorts behind every real assignment
            idx = jnp.where(alive, idx, R)
            gates = jnp.where(alive, gates, 0.0)
        flat = idx.reshape(T * k)
        counts = jnp.sum(
            flat[:, None] == jnp.arange(R, dtype=flat.dtype)[None, :],
            axis=0, dtype=jnp.int32)                           # [R]
        routed_counts = counts
        if cfg.holds_a_share:
            # the held experts' own numbering; the others sort behind
            # (a dead token's R lies beyond them too)
            here = idx - cfg.moe_experts_first
            mine = (here >= 0) & (here < E)
            gates = jnp.where(mine, gates, 0.0)
            flat = jnp.where(mine, here, E).reshape(T * k)
            counts = routed_counts[
                cfg.moe_experts_first:cfg.moe_experts_first + E]

    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(flat, stable=True)                 # [T*k]
        rows = xf.astype(cdtype)[order // k]                   # [T*k, h]

    with jax.named_scope("moe_experts"):
        experts, sizes = params["experts"], counts
        if layer is not None:
            experts, sizes = _layer_of_stacked(experts, layer, counts, cdtype)
        w_in = dequantize_weight(experts, "w_in", cdtype)
        w_out = dequantize_weight(experts, "w_out", cdtype)
        mid = apply_mlp_activation(_grouped_matmul(rows, w_in, sizes), cfg)
        if mid.shape[-1] != w_out.shape[-2]:
            # the columns of a laid-out width (``laid_width``): zeros
            mid = mid[:, :w_out.shape[-2]]
        y = _grouped_matmul(mid, w_out, sizes)                 # [T*k, h]

    with jax.named_scope("moe_combine"):
        # each row of y moves ONCE, in y's own dtype, to where its token's
        # j-th choice lies (choice-major: row j * T + t), and the k
        # choices are added in turn, cast and gated inside the one fused
        # sum: nothing in float32 has T * k rows.  A [T, k, h] array
        # would be padded to 16 choices on the chip wherever k is not 8
        slot = jnp.arange(T * k, dtype=order.dtype)
        # the inverse of the sort: where assignment t * k + j went
        there = (order % k) * T + order // k
        back = jnp.zeros_like(order).at[there].set(slot)       # [k*T]
        y = y[back]                                            # [k*T, h]

        def choice(j):
            gate = gates[:, j, None]
            term = y[j * T:(j + 1) * T].astype(jnp.float32) * gate
            # a choice sent nowhere has gate 0 (moe_route) and its row,
            # past sum(counts), came back undefined (_grouped_matmul)
            return jnp.where(gate != 0.0, term, 0.0)

        out = choice(0)
        for j in range(1, k):
            out = out + choice(j)

    out = out.reshape(b, s, h)
    shared = _shared_mlp(x, params, cfg)
    if shared is not None:
        out = out + shared.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(routed_counts), 1).astype(jnp.float32)
    aux = _aux_losses(logits, probs,
                      routed_counts.astype(jnp.float32) / total)
    return out.astype(x.dtype), aux, routed_counts


def moe_mlp(
    x: jax.Array,
    params,
    cfg: TransformerConfig,
):
    """x [b, s, h] -> (out [b, s, h], aux [2] fp32 = [load-balance, z]).

    Dispatch/combine einsum pipeline (all shapes static):
      router probs [b,s,E] -> top-k gates -> position-in-expert by cumsum
      -> dispatch mask [b, s*k, E, c] -> expert batches [E, b, c, h]
      -> per-expert FFN (tp-sharded) -> combine back to [b, s, h].
    """
    if cfg.holds_a_share:
        raise NotImplementedError(
            "a share of the router's experts (moe_router_experts) is not "
            "implemented for the capacity einsum (training)")
    E, k = cfg.num_experts, cfg.moe_top_k
    b, s, h = x.shape
    c = moe_capacity(cfg, s)
    cdtype = cfg.compute_jnp_dtype

    logits, probs, gates, idx = _route(x, params, cfg)         # [b, s, k]

    # --- position-in-expert over flattened (s, k) slots, token-major so
    # earlier tokens win the buffer (Switch priority) ---
    oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)             # [b, s, k, E]
    ohf = oh.reshape(b, s * k, E)
    pos = jnp.cumsum(ohf, axis=1) - ohf                        # [b, s*k, E]
    slot_pos = jnp.sum(pos * ohf, axis=-1)                     # [b, s*k]
    keep = (slot_pos < c).astype(jnp.float32)
    dispatch_f = ohf * keep[..., None]                         # [b, s*k, E]
    oh_pos = jax.nn.one_hot(slot_pos.astype(jnp.int32), c,
                            dtype=jnp.float32)                 # [b, s*k, c]

    disp4 = jnp.einsum("bte,btc->btec", dispatch_f, oh_pos)
    disp4 = disp4.reshape(b, s, k, E, c)
    gates_tok = gates.reshape(b, s, k)
    combine = jnp.einsum("bskec,bsk->bsec", disp4, gates_tok)  # [b, s, E, c]
    disp_tok = jnp.sum(disp4, axis=2)                          # [b, s, E, c]

    # --- dispatch: [E, b, c, h], expert dim onto the dp axis (all-to-all) ---
    ex = _cfg_expert_axis(cfg)
    expert_in = jnp.einsum(
        "bsec,bsh->ebch", disp_tok.astype(cdtype), x.astype(cdtype))
    expert_in = constrain(expert_in, ex, None, None, None)

    # --- per-expert FFN, tp-sharded like the dense MLP ---
    w_in = dequantize_weight(params["experts"], "w_in", cdtype)
    w_out = dequantize_weight(params["experts"], "w_out", cdtype)
    mid = jnp.einsum("ebch,ehf->ebcf", expert_in, w_in)
    mid = constrain(mid, ex, None, None, "ffn")
    mid = apply_mlp_activation(mid, cfg)
    if mid.shape[-1] != w_out.shape[-2]:
        mid = mid[..., :w_out.shape[-2]]
    expert_out = jnp.einsum("ebcf,efh->ebch", mid, w_out)
    expert_out = constrain(expert_out, ex, None, None, None)

    # --- combine (weighted un-dispatch) ---
    out = jnp.einsum("ebch,bsec->bsh", expert_out, combine.astype(cdtype))
    shared = _shared_mlp(x, params, cfg)
    if shared is not None:
        out = out + shared.astype(out.dtype)

    frac = jnp.mean(oh.reshape(-1, E), axis=0)                 # [E], sums to 1
    aux = _aux_losses(logits, probs, frac)

    return out.astype(x.dtype), aux
