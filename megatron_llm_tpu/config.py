"""Model / parallelism configuration.

The reference spreads configuration over a 225-flag argparse namespace
(``megatron/arguments.py``) consumed through a global singleton.  Here the
model-shape portion is a frozen, hashable dataclass so it can be a static
argument to ``jax.jit`` — everything the compiled step function needs to
specialise on lives here.  The argparse-compatible CLI surface lives in
``megatron_llm_tpu/arguments.py`` and is *lowered* into this dataclass.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from enum import Enum
from typing import Optional, Tuple

import jax.numpy as jnp


class PositionEmbeddingType(str, Enum):
    # reference: megatron/model/enums.py:20-23
    rotary = "rotary"
    learned_absolute = "learned_absolute"
    # no position embedding at all ("nope"): nothing rotates, nothing is
    # added; what orders the tokens is the causal mask and, in a hybrid,
    # the recurrence of its state-space layers
    none = "none"


class AttnMaskType(str, Enum):
    # reference: megatron/model/enums.py (padding/causal)
    padding = "padding"
    causal = "causal"


DTYPES = {
    "fp32": jnp.float32,
    "fp16": jnp.float16,
    "bf16": jnp.bfloat16,
}


@dataclass(frozen=True)
class ParallelConfig:
    """Mesh shape + parallelism behaviour.

    Replaces the process-group bookkeeping of
    ``megatron/core/parallel_state.py:51-205``: on TPU the entire fabric is
    one ``Mesh(devices, ('dp', 'pp', 'tp'))`` and these sizes are the axis
    lengths.
    """

    tensor_model_parallel_size: int = 1
    pipeline_model_parallel_size: int = 1
    data_parallel_size: int = 1
    # reference: --num_layers_per_virtual_pipeline_stage (arguments.py:121-132)
    virtual_pipeline_model_parallel_size: Optional[int] = None
    # Megatron-style sequence parallelism (activation sharding along the
    # sequence axis in non-TP regions).  reference: arguments.py:698.
    sequence_parallel: bool = False
    # ZeRO-1: shard optimizer state over the dp axis.
    # reference: --use_distributed_optimizer (distrib_optimizer.py)
    use_distributed_optimizer: bool = False
    # context parallelism (ring attention over the cp mesh axis) — a
    # TPU-native extension; the reference has none (SURVEY §5.7)
    context_parallel_size: int = 1
    # Expert parallelism size (MoE). The reference has no MoE; we support it
    # as a TPU-native extension (axis folded into dp during non-MoE ops).
    expert_model_parallel_size: int = 1
    # Multi-slice (MegaScale-tier): number of TPU pod slices joined over
    # DCN; the mesh gains an outer 'slice' axis and data parallelism is
    # num_slices * data_parallel_size (data_parallel_size stays the
    # *per-slice* dp, matching the mesh's dp axis).
    num_slices: int = 1
    # Stage the train step's one gradient reduction ICI-first/DCN-second
    # (multislice.hierarchical_psum). Resolved at arg validation: on for
    # pure-DP multi-slice runs, off (one flat psum over ('slice','dp'))
    # when in-slice model parallelism is active or
    # --multislice_flat_reduce is passed.
    multislice_hierarchical: bool = False

    @property
    def world_size(self) -> int:
        return (
            self.tensor_model_parallel_size
            * self.pipeline_model_parallel_size
            * self.data_parallel_size
            * self.context_parallel_size
            * self.num_slices
        )


# what a layer type may be (TransformerConfig.layer_types): two windows
# of attention, and the two mixers of a hybrid as its config publishes
# them ('attention' attends every key, as 'full' does; 'mamba' is a
# Mamba-2 state-space mixer, models/mamba.py).  'moe' is an expert layer
# ALONE: with it among the types every layer is ONE sublayer under ONE
# norm, a mixer or an expert layer (``one_sublayer``).  'conv' is a gated
# short convolution (models/short_conv.py; lfm2's ``conv`` beside its
# ``full_attention``, which is 'attention' here): the other mixer that
# carries a state from token to token.  'retention' is a power-retention
# mixer (models/retention.py; brumby's every layer): attention's
# projections over a recurrent state a key-value head and no key kept.
# 'gated_delta' is a gated delta-rule mixer (models/gated_delta.py;
# qwen3_next's ``linear_attention`` beside its ``full_attention``, which
# is 'attention' here): a state a value head that is UPDATED BY WHAT IT
# HOLDS (what it already answers for a key is taken off before the key's
# value is written), behind a short convolution as Mamba's
LAYER_TYPES = ("sliding", "full", "mamba", "attention", "moe", "conv",
               "retention", "gated_delta")
# the layer types whose mixer carries a STATE a request in a slot and no
# pages (``TransformerConfig.state_layer``: what ops/paged_kv.py asks)
STATE_TYPES = ("mamba", "conv", "retention", "gated_delta")
# the letters of a published ``hybrid_override_pattern``, a layer each
PATTERN_LETTERS = {"M": "mamba", "*": "attention", "E": "moe"}


def pattern_layer_types(pattern: str) -> Tuple[str, ...]:
    """A published ``hybrid_override_pattern`` (a letter a layer: ``M`` a
    Mamba-2 mixer, ``*`` an attention mixer, ``E`` an expert layer) as
    ``layer_types``, every layer of the depth in ONE period: such a
    pattern need not repeat."""
    if "-" in pattern:
        raise ValueError(
            "a dense MLP layer ('-' in hybrid_override_pattern) is not "
            "implemented: the layer kinds are M (mamba), * (attention) "
            "and E (moe)")
    unknown = sorted(set(pattern) - set(PATTERN_LETTERS))
    if unknown or not pattern:
        raise ValueError(f"hybrid_override_pattern is letters of "
                         f"{'|'.join(PATTERN_LETTERS)}, got {pattern!r}")
    return tuple(PATTERN_LETTERS[c] for c in pattern)


# --- what runs with what ------------------------------------------------
# Whether a model's mechanism A runs with B, another mechanism or a
# feature of the runtime, is answered HERE and nowhere else: ``RUNS_WITH``
# has a row for each mechanism, of what it does not run with, and
# ``refusal`` reads it.  A new mechanism adds its predicate and its row;
# the guards INSIDE a mechanism (a layer handed a cache it cannot use)
# stay at the point that would otherwise compute garbage.
#
# The runtime's features, by the names the sentences use.  Whoever turns
# one on asks ``refusal`` with it: ``serving/engine.py`` (its
# EngineConfig's), ``ops/paged_kv.py::init_pools`` (the int8 pool),
# ``models/gpt.py`` (the parallelism the mesh has in force),
# ``models/transformer.py::transformer_stack`` (training),
# ``text_generation/generation.py::init_kv_caches`` (the rolling cache).
VERIFY_STEP = "the speculative verify step"
INT8_POOL = "the int8 KV pool"
HOST_TIER = "the host KV tier"
PREEMPTION = "preemption"
PREFIX_CACHE = "the prefix cache"
TENSOR_PARALLEL = "tensor parallelism (tp > 1)"
MODEL_PARALLEL = "tensor or pipeline parallelism (tp > 1, pp > 1)"
TRAINING = "training"
ROLLING_CACHE = "the legacy rolling decode cache"
FEATURES = (VERIFY_STEP, INT8_POOL, HOST_TIER, PREEMPTION, PREFIX_CACHE,
            TENSOR_PARALLEL, MODEL_PARALLEL, TRAINING, ROLLING_CACHE)
# the features a model that does not run with them is not refused but
# runs WITHOUT: whoever serves it turns the feature off and says so
TURNED_OFF = (PREFIX_CACHE,)

# What a config may have, by the names the sentences use, each with its
# predicate over the config (as ``__post_init__`` has normalised it).
SPARSE = "sparse attention (dsa_index_heads > 0)"
LATENT = "latent attention (kv_lora_rank)"
SPARSE_LATENT = ("the selection over latents (dsa_index_heads > 0 with "
                 "kv_lora_rank)")
TYPED = "a layer type per layer (layer_types)"
STATE_SPACE = "state-space layers ('mamba' among layer_types)"
SHORT_CONV = "gated short-convolution layers ('conv' among layer_types)"
RETENTION = "power-retention layers ('retention' among layer_types)"
GATED_DELTA = "gated delta-rule layers ('gated_delta' among layer_types)"
ONE_SUBLAYER = "layers of one sublayer ('moe' among layer_types)"
FIRST_DENSE = "leading dense layers (moe_first_dense_layers)"
SHARE = "a share of the router's experts (moe_router_experts)"
EXPERTS = "experts (num_experts > 1)"
QK_NORM_WHOLE = "qk_norm (over the whole projection)"
QK_NORM_PER_HEAD = "qk_norm_per_head"
SLIDING = "a sliding window (sliding_window_size)"
NOT_ROTARY = "a position embedding that is not rotary"
SECTIONED = "sectioned rope (rope_sections)"
ROPE_SCALING = "rope scaling"
BIASES = "linear biases (add_bias_linear)"
QKV_BIAS = "a bias on the QKV projections (add_qkv_bias)"
PARALLEL_ATTN = "parallel_attn"
POST_LN = "post-LN (use_post_ln)"
GATE = "an attention output gate (attention_output_gate)"
OUTPUT_NORMS = "norms on both sublayers' outputs (sublayer_output_norm)"
LOOPED = "a stack run several times (loop_steps > 1)"
ROPE_TYPES = "layer types that do not rotate (rope_layer_types)"
OTHER_TYPES = "layer types other than 'mamba', 'attention' and 'moe'"
CONV_OTHER_TYPES = "layer types other than 'conv' and 'attention'"
RETENTION_OTHER_TYPES = "layer types other than 'retention'"
DELTA_OTHER_TYPES = "layer types other than 'gated_delta' and 'attention'"
HAS = {
    SPARSE: lambda c: c.dsa_index_heads > 0,
    LATENT: lambda c: c.kv_lora_rank is not None,
    SPARSE_LATENT: lambda c: (c.dsa_index_heads > 0
                              and c.kv_lora_rank is not None),
    TYPED: lambda c: c.layer_types is not None,
    STATE_SPACE: lambda c: c.state_space,
    SHORT_CONV: lambda c: c.short_conv,
    RETENTION: lambda c: c.retention,
    GATED_DELTA: lambda c: c.gated_delta,
    ONE_SUBLAYER: lambda c: c.one_sublayer,
    FIRST_DENSE: lambda c: c.moe_first_dense_layers > 0,
    SHARE: lambda c: c.holds_a_share,
    EXPERTS: lambda c: c.num_experts > 1,
    QK_NORM_WHOLE: lambda c: c.qk_norm,
    QK_NORM_PER_HEAD: lambda c: c.qk_norm_per_head,
    SLIDING: lambda c: c.sliding_window_size is not None,
    NOT_ROTARY: lambda c: (c.position_embedding_type
                           != PositionEmbeddingType.rotary),
    SECTIONED: lambda c: c.rope_sections is not None,
    ROPE_SCALING: lambda c: (c.rope_yarn_scaling is not None
                             or c.rope_llama3_scaling is not None
                             or c.rope_scaling_factor != 1.0),
    BIASES: lambda c: c.add_bias_linear,
    QKV_BIAS: lambda c: c.add_qkv_bias,
    PARALLEL_ATTN: lambda c: c.parallel_attn,
    POST_LN: lambda c: c.use_post_ln,
    GATE: lambda c: c.attention_output_gate,
    OUTPUT_NORMS: lambda c: c.sublayer_output_norm,
    LOOPED: lambda c: c.loop_steps > 1,
    ROPE_TYPES: lambda c: c.rope_layer_types is not None,
    OTHER_TYPES: lambda c: bool(set(c.layer_types or ())
                                - {"mamba", "attention", "moe"}),
    CONV_OTHER_TYPES: lambda c: bool(set(c.layer_types or ())
                                     - {"conv", "attention"}),
    RETENTION_OTHER_TYPES: lambda c: bool(set(c.layer_types or ())
                                          - {"retention"}),
    DELTA_OTHER_TYPES: lambda c: bool(set(c.layer_types or ())
                                      - {"gated_delta", "attention"}),
}

# THE TABLE: what a model has, and everything it does not run with.  A
# config is told the first square it falls in, so a row that says more
# of a model (state-space layers) stands before the row that says less
# (a layer type per layer).
RUNS_WITH = (
    (SPARSE_LATENT, (TRAINING,)),
    (SPARSE, (SLIDING, NOT_ROTARY, VERIFY_STEP, INT8_POOL,
              TENSOR_PARALLEL)),
    (ONE_SUBLAYER, (OTHER_TYPES, TRAINING, MODEL_PARALLEL, VERIFY_STEP, INT8_POOL, HOST_TIER, PREEMPTION,
                    PREFIX_CACHE)),
    (RETENTION, (RETENTION_OTHER_TYPES, BIASES, QKV_BIAS, PARALLEL_ATTN,
                 POST_LN, LATENT, SPARSE, SLIDING, GATE, OUTPUT_NORMS,
                 EXPERTS, TRAINING, MODEL_PARALLEL, VERIFY_STEP, INT8_POOL,
                 HOST_TIER, PREEMPTION, PREFIX_CACHE)),
    (GATED_DELTA, (DELTA_OTHER_TYPES, BIASES, PARALLEL_ATTN, POST_LN,
                   LATENT, SPARSE, SLIDING, OUTPUT_NORMS, TRAINING,
                   MODEL_PARALLEL, VERIFY_STEP, INT8_POOL, HOST_TIER,
                   PREEMPTION, PREFIX_CACHE)),
    (SHORT_CONV, (CONV_OTHER_TYPES, BIASES, PARALLEL_ATTN, POST_LN, LATENT,
                  GATE, OUTPUT_NORMS, TRAINING, MODEL_PARALLEL, VERIFY_STEP,
                  INT8_POOL, HOST_TIER, PREEMPTION, PREFIX_CACHE)),
    (STATE_SPACE, (OTHER_TYPES, BIASES, PARALLEL_ATTN, POST_LN, LATENT,
                   VERIFY_STEP, INT8_POOL, HOST_TIER, PREEMPTION,
                   MODEL_PARALLEL)),
    (LOOPED, (TYPED, LATENT, SPARSE, EXPERTS, TRAINING, MODEL_PARALLEL,
              VERIFY_STEP, INT8_POOL, HOST_TIER)),
    (GATE, (LATENT, TRAINING, MODEL_PARALLEL, VERIFY_STEP, INT8_POOL,
            HOST_TIER)),
    (OUTPUT_NORMS, (ONE_SUBLAYER, PARALLEL_ATTN, POST_LN, TRAINING,
                    MODEL_PARALLEL, VERIFY_STEP, INT8_POOL, HOST_TIER)),
    (ROPE_TYPES, (NOT_ROTARY, TRAINING)),
    (TYPED, (SPARSE, SECTIONED, VERIFY_STEP, INT8_POOL, HOST_TIER,
             PREFIX_CACHE, MODEL_PARALLEL, ROLLING_CACHE)),
    (FIRST_DENSE, (ONE_SUBLAYER, MODEL_PARALLEL)),
    (LATENT, (NOT_ROTARY, SLIDING, TYPED, QK_NORM_WHOLE,
              QK_NORM_PER_HEAD, SECTIONED, ROPE_SCALING, BIASES, QKV_BIAS,
              PARALLEL_ATTN, VERIFY_STEP, INT8_POOL, HOST_TIER,
              MODEL_PARALLEL)),
    (QK_NORM_WHOLE, (QK_NORM_PER_HEAD, TENSOR_PARALLEL)),
    (EXPERTS, (BIASES,)),
    (SHARE, (MODEL_PARALLEL,)),
)
# how a square's sentence ends, where it says more than the two names
TAILS = {
    (SPARSE_LATENT, TRAINING):
        " (no backward through a choice made inside latent attention is "
        "held to anything)",
    (ONE_SUBLAYER, OTHER_TYPES):
        " (a 'moe' layer type goes with 'mamba' and 'attention' layers)",
    (ONE_SUBLAYER, TRAINING):
        " (the capacity einsum holds no share of the experts, and no "
        "backward through such a stack is held to anything)",
    (ONE_SUBLAYER, PREEMPTION):
        " (no snapshot of a request's state is kept): set preemption off "
        "(--serve_preemption=0)",
    (ONE_SUBLAYER, PREFIX_CACHE):
        " adopts nothing (a state-space layer's state at a prefix's end "
        "is not kept)",
    (STATE_SPACE, OTHER_TYPES):
        " (a 'mamba' layer type goes with 'moe' and 'attention' layers only)",
    (STATE_SPACE, PREEMPTION):
        " (no snapshot of a request's state is kept): set preemption off "
        "(--serve_preemption=0)",
    (RETENTION, RETENTION_OTHER_TYPES):
        " (a stack that mixes a state of this size with pages, or with "
        "'mamba' or 'conv' layers' states, is held to nothing: every "
        "layer of the depth is 'retention')",
    (RETENTION, SLIDING):
        " (a recurrent state forgets by its gates, not by a window)",
    (RETENTION, EXPERTS):
        " (the published stack is dense; a sparse MLP beside a state of "
        "this size is held to nothing)",
    (RETENTION, TRAINING):
        " (no backward through the chunk form is held to anything, and "
        "packed documents would need the state reset at each boundary)",
    (RETENTION, MODEL_PARALLEL):
        " (the state would be split by key-value head, and a stage would "
        "hold its layers' states alone)",
    (RETENTION, VERIFY_STEP):
        " (a rejected draft's updates of the state would have to be "
        "taken back)",
    (RETENTION, INT8_POOL): " (there is no key and no value to quantise)",
    (RETENTION, HOST_TIER): " (there is no page to spill)",
    (RETENTION, PREEMPTION):
        " (no snapshot of a request's state is kept): set preemption off "
        "(--serve_preemption=0)",
    (RETENTION, PREFIX_CACHE):
        " adopts nothing (a retention layer's state at a prefix's end is "
        "not kept)",
    (GATED_DELTA, DELTA_OTHER_TYPES):
        " (a 'gated_delta' layer type goes with 'attention' layers only: "
        "a stack that holds it beside 'mamba', 'conv' or 'retention' "
        "layers, two states of two shapes a slot, or beside a window "
        "group, is held to nothing)",
    (GATED_DELTA, SLIDING):
        " (a recurrent state forgets by its gates, not by a window)",
    (GATED_DELTA, TRAINING):
        " (no backward through the triangular solve of the chunk form is "
        "held to anything, and packed documents would need the state "
        "reset at each boundary)",
    (GATED_DELTA, MODEL_PARALLEL):
        " (the state and the convolution's channels would be split by "
        "value head, and a stage would hold its layers' states alone)",
    (GATED_DELTA, VERIFY_STEP):
        " (a rejected draft's updates were written OVER the state they "
        "read: they would have to be taken back)",
    (GATED_DELTA, INT8_POOL):
        " (the int8 pool's scales are a page's, and a state is no page)",
    (GATED_DELTA, HOST_TIER):
        " (a page of keys without the state at its end resumes nothing)",
    (GATED_DELTA, PREEMPTION):
        " (no snapshot of a request's state is kept): set preemption off "
        "(--serve_preemption=0)",
    (GATED_DELTA, PREFIX_CACHE):
        " adopts nothing (a delta-rule layer's state at a prefix's end is "
        "not kept)",
    (SHORT_CONV, CONV_OTHER_TYPES):
        " (a 'conv' layer type goes with 'attention' layers only: a stack "
        "that holds 'mamba' and 'conv' together, two states of two shapes "
        "a slot, is held to nothing)",
    (SHORT_CONV, TRAINING):
        " (no backward through the carried columns is held to anything, "
        "and packed documents would need them reset at each boundary)",
    (SHORT_CONV, MODEL_PARALLEL):
        " (the convolution's channels and its slot's columns would be "
        "split with the hidden width, and a stage would hold the tail of "
        "a pattern that does not repeat)",
    (SHORT_CONV, VERIFY_STEP):
        " (a rejected draft's columns would have to be taken back)",
    (SHORT_CONV, INT8_POOL):
        " (two 64-wide heads share a 128-lane row of the pool, and the "
        "int8 pool's scales are a row's)",
    (SHORT_CONV, HOST_TIER):
        " (a page of keys without the columns at its end resumes nothing)",
    (SHORT_CONV, PREEMPTION):
        " (no snapshot of a request's columns is kept): set preemption "
        "off (--serve_preemption=0)",
    (SHORT_CONV, PREFIX_CACHE):
        " adopts nothing (a convolution layer's columns at a prefix's end "
        "are not kept)",
    (LOOPED, TYPED):
        " (a pass's planes of two page groups, or a state carried from "
        "pass to pass, are held to nothing)",
    (LOOPED, LATENT):
        " (a latent pool a pass is held to nothing)",
    (LOOPED, SPARSE):
        " (an indexer's keys a pass are held to nothing)",
    (LOOPED, EXPERTS):
        " (the published stack is dense; a router asked once a pass is "
        "held to nothing)",
    (LOOPED, TRAINING):
        " (the published objective weighs every pass's loss by the exit "
        "distribution with an entropy term: it is not built)",
    (LOOPED, MODEL_PARALLEL):
        " (under pipeline stages a token goes round the ring once a pass: "
        "the schedule is not built)",
    (LOOPED, VERIFY_STEP):
        " (a draft's rows in every pass's planes are held to nothing)",
    (LOOPED, INT8_POOL):
        " (no int8 plane of a pass is held to the reference)",
    (LOOPED, HOST_TIER):
        " (a page spilled with every pass's planes is held to nothing)",
    (GATE, LATENT):
        " (latent_attention has no fourth projection and no product)",
    (GATE, TRAINING): " (no backward through the gate is held to anything)",
    (OUTPUT_NORMS, ONE_SUBLAYER):
        " (a layer of one sublayer has one norm, on its input)",
    (OUTPUT_NORMS, PARALLEL_ATTN):
        " (one residual add of both sublayers: which output would be "
        "normed?)",
    (OUTPUT_NORMS, POST_LN): " (use_post_ln norms the STREAM after the add)",
    (OUTPUT_NORMS, TRAINING):
        " (no backward through the output norms is held to anything)",
    (ROPE_TYPES, NOT_ROTARY): " (nothing rotates there, on any layer)",
    (ROPE_TYPES, TRAINING):
        " (no backward through a stack that rotates on some layers only "
        "is held to anything)",
    (FIRST_DENSE, ONE_SUBLAYER):
        " (a layer of one sublayer has no MLP to keep dense)",
    (TYPED, PREFIX_CACHE):
        " adopts nothing (a prefix's window pages, and a state-space "
        "layer's state at its end, are not kept)",
    (TYPED, ROLLING_CACHE):
        " (a ring of window slots on EVERY layer: a layer that sees every "
        "key has none)",
    (QK_NORM_WHOLE, QK_NORM_PER_HEAD): " (two forms of one norm: choose one)",
    (QK_NORM_WHOLE, TENSOR_PARALLEL):
        " (its mean square is over all the heads, which tensor parallelism "
        "splits)",
    (EXPERTS, BIASES): ": set add_bias_linear=False",
}


def refusal(cfg, features=()) -> Optional[str]:
    """What the model config ``cfg`` is told of the first square of
    ``RUNS_WITH`` it falls in, given the runtime ``features`` that are on
    (names of ``FEATURES``; none: the model against itself), or None: it
    runs with all of them."""
    for has, whats in RUNS_WITH:
        if not HAS[has](cfg):
            continue
        for what in whats:
            if what in features if what in FEATURES else HAS[what](cfg):
                said = what if what in TURNED_OFF else (
                    f"not implemented with {what}")
                return f"{has}: {said}{TAILS.get((has, what), '')}"
    return None


@dataclass(frozen=True)
class TransformerConfig:
    """Architecture hyper-parameters.

    Field names mirror the reference flags (``megatron/arguments.py``) so the
    CLI and checkpoint-args machinery map 1:1.
    """

    num_layers: int = 2
    hidden_size: int = 128
    num_attention_heads: int = 4
    # GQA/MQA: number of KV heads (reference: --num_attention_heads_kv,
    # packed QKV layout at megatron/model/transformer.py:334-365,458-465).
    num_attention_heads_kv: Optional[int] = None
    ffn_hidden_size: Optional[int] = None
    kv_channels: Optional[int] = None
    seq_length: int = 512
    max_position_embeddings: Optional[int] = None
    padded_vocab_size: int = 50304

    # --- embeddings / head ---
    position_embedding_type: PositionEmbeddingType = PositionEmbeddingType.learned_absolute
    # RoPE position-interpolation context extension
    # (reference: megatron/model/positional_embeddings.py:7-14, --rope_scaling_factor)
    rope_scaling_factor: float = 1.0
    rope_theta: float = 10000.0
    # Llama-3.1 NTK-by-parts rope remap (beyond-reference; HF
    # rope_scaling={'rope_type': 'llama3', ...}).  None = off; otherwise
    # (factor, low_freq_factor, high_freq_factor,
    # original_max_position) — a tuple so the config stays hashable
    # (it rides jit static args).
    rope_llama3_scaling: Optional[Tuple[float, float, float, int]] = None
    # YaRN rope remap (HF rope_type 'yarn'): (factor,
    # original_max_position, beta_fast, beta_slow, attention_factor); the
    # last multiplies cos and sin.  ``rope_yarn_layer_types`` names the
    # layer types it applies to (None: every layer); the others keep the
    # plain embedding
    rope_yarn_scaling: Optional[Tuple[float, int, float, float, float]] = None
    rope_yarn_layer_types: Optional[Tuple[str, ...]] = None
    # the layer types whose queries and keys rotate (None: every type);
    # a layer of another type carries NO positions: what orders its keys
    # is the causal mask alone (afmoe: the 'sliding' layers rotate, the
    # 'full' ones do not)
    rope_layer_types: Optional[Tuple[str, ...]] = None
    # reference: --no_tie_embed_logits -> untied lm_head
    # (megatron/model/language_model.py:436-457)
    tie_embed_logits: bool = True
    # tokentype (segment) embeddings for BERT-style models
    # (reference: Embedding tokentype path, language_model.py:163-262)
    num_tokentypes: int = 0

    # --- norm / activation / structure ---
    # 'layernorm' | 'rmsnorm'  (reference: megatron/model/fused_layer_norm.py)
    normalization: str = "layernorm"
    layernorm_epsilon: float = 1e-5
    # post-LN (original transformer) vs pre-LN
    # (reference: --use_post_ln, transformer.py:660-664)
    use_post_ln: bool = False
    # GLU family: None | 'swiglu' | 'geglu' | 'reglu' | 'liglu'
    # (reference: megatron/model/glu_activations.py:8-49)
    glu_activation: Optional[str] = None
    # non-GLU MLP activation: 'tanh' = approximate gelu (GPT-2/Megatron
    # bias-gelu fusion polynomial), 'exact' = erf gelu (Falcon / F.gelu)
    gelu_variant: str = "tanh"
    # the ungated MLP's nonlinearity where it is no gelu: 'relu2' =
    # relu(x)^2 (two matrices an MLP: experts, a shared MLP and a dense
    # MLP alike; ops/activations.py)
    mlp_activation: str = "gelu"
    # bias toggles (reference: --use_bias / --no_bias in arguments.py)
    add_bias_linear: bool = True
    # Falcon-style parallel attention+MLP (reference: transformer.py:635-664)
    parallel_attn: bool = False
    # Falcon-40B parallel layernorm (reference: transformer.py:804-845)
    parallel_layernorm: bool = False
    # Mistral sliding-window attention (reference: transformer.py:528-537).
    # With ``layer_types`` it is the window of the 'sliding' layers only
    sliding_window_size: Optional[int] = None
    # a layer type per layer, as data: ONE period of types, repeated over
    # the depth ('sliding': a query attends the sliding_window_size keys
    # up to itself; 'full': every key up to itself).  None: the stack is
    # one period of one type, the window (if any) on every layer.  With
    # 'moe' among them a layer is ONE sublayer, ``x + f(norm(x))``: a
    # 'mamba' or 'attention' layer has no MLP, a 'moe' layer no mixer
    layer_types: Optional[Tuple[str, ...]] = None
    # the attention's output (before its output projection) times
    # ``sigmoid(gate(u))``, u the layer's normed input: a fourth
    # projection ``[hidden, heads x head_dim]`` (afmoe's ``gate_proj``),
    # fused into ``query_key_value`` a key-value group at a time
    attention_output_gate: bool = False
    # FOUR norms a layer: each sublayer's OUTPUT is normed as well as its
    # input, ``x + norm(f(norm(x)))`` for attention and for the MLP (a
    # "sandwich").  The two further leaves are ``attention_output_norm``
    # and ``mlp_output_norm``; ``post_attention_norm`` stays what it is
    # everywhere, the norm BEFORE the MLP
    sublayer_output_norm: bool = False
    # a LOOPED stack (ouro's ``total_ut_steps``): the ``num_layers`` layers
    # run ``loop_steps`` times over the SAME weights, the final norm after
    # EACH pass, and pass t attends pass t's keys and values alone: a
    # token holds ``cache_layers`` planes.  After each pass an exit gate
    # (``exit_gate`` of the stack's params) reads the normed stream;
    # ``early_exit_threshold`` is the cumulative exit mass a token would
    # leave at, and only 1.0 (every token runs every pass) is built
    loop_steps: int = 1
    early_exit_threshold: float = 1.0

    # --- dropout / init ---
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    init_method_std: float = 0.02
    # reference --init_method_xavier_uniform: glorot-uniform linear init
    init_method_xavier_uniform: bool = False
    # divide output-layer init by sqrt(2*num_layers)
    # (reference: --init_method_xavier_uniform absent; scaled init in layers)
    use_scaled_init_method: bool = True

    # --- dtypes ---
    params_dtype: str = "fp32"          # storage dtype of the trained params
    compute_dtype: str = "fp32"         # activation/computation dtype
    # upcast LN/RMSNorm compute to fp32 (reference rmsnorm does fp32 compute,
    # fused_layer_norm.py:125-139)
    norm_in_fp32: bool = True

    # --- attention numerics ---
    attention_softmax_in_fp32: bool = True
    # divide qk^T by sqrt(head_dim) (standard)
    use_flash_attn: bool = True         # Pallas flash-attention kernel
    use_fused_rmsnorm: bool = True      # Pallas fused RMSNorm kernel
    use_fused_layernorm: bool = True    # Pallas fused LayerNorm kernel
    # chunked head-matmul + CE (never materializes [tokens, vocab] logits);
    # applies on the unsharded-vocab (tp=1) training path.  Default OFF:
    # measured on v5e at 32k vocab it saves <0.1 GB (XLA already schedules
    # the logits+CE region tightly) and costs ~3% MFU to scan
    # serialization — worth enabling only for much larger vocabularies
    fused_lm_cross_entropy: bool = False
    fused_ce_chunk_size: int = 8192

    # --- recompute (reference: transformer.py:1110-1176) ---
    # None | 'uniform' | 'block' | 'selective'
    recompute_granularity: Optional[str] = None
    recompute_num_layers: int = 1

    # --- lima dropout (reference: --lima_dropout, transformer.py) ---
    lima_dropout: bool = False

    # --- mixture of experts (TPU-native extension; the reference has no
    # MoE — SURVEY §2.2 marks EP "absent").  Experts replace the dense MLP
    # in every layer when num_experts > 1; expert weights are sharded over
    # the dp mesh axis ('expert' logical axis, EP folded into dp) and
    # tokens reach their experts through XLA all-to-alls inserted by GSPMD
    # around the dispatch/combine einsums (models/moe.py). ---
    num_experts: int = 0                 # 0/1 = dense MLP
    moe_top_k: int = 2                   # experts per token
    moe_capacity_factor: float = 1.25    # per-expert buffer slack
    moe_min_capacity: int = 4            # capacity floor (decode s=1)
    moe_aux_loss_coeff: float = 1e-2     # load-balance loss weight
    moe_z_loss_coeff: float = 0.0        # router logit z-loss weight
    # expert-dim placement: "auto" derives from the live mesh (E % dp == 0)
    # and is resolved ONCE at model construction (GPTModel.__init__) so
    # param-spec time and trace time cannot disagree if the mesh changes in
    # between (round-3 advisor finding); "expert" / "replicated" force it.
    moe_expert_axis: str = "auto"
    # renormalise the chosen experts' gates to sum to 1 (Mixtral: the
    # softmax over the chosen ones); False uses the softmax over all
    # experts as it is (OLMoE), so a token's gates sum to less than 1
    norm_topk_prob: bool = True
    # an expert's width where it is not ``ffn_hidden_size`` (a config
    # that publishes a dense ``intermediate_size`` beside the experts'
    # ``moe_intermediate_size``; with every layer sparse the dense width
    # shapes nothing).  None: ``ffn_hidden_size``
    moe_ffn_hidden_size: Optional[int] = None
    # how the router scores the experts: 'softmax' over all of them, or
    # an independent 'sigmoid' an expert (DeepSeek-V3's router)
    moe_score_function: str = "softmax"
    # a learned-by-balancing bias an expert (``e_score_correction_bias``:
    # a buffer ``[E]`` a layer in the param tree) that is added to the
    # scores for the CHOICE of the top-k only; the gates are the scores
    moe_choice_bias: bool = False
    # the spread a FRESH model's choice bias is drawn at (a checkpoint
    # overwrites the buffer); None: ``models/moe.py::_CHOICE_BIAS_STD``,
    # 0.1.  A published bias is what balancing the experts' load moved
    # it to, so a drawn one UNBALANCES a random router: at 0.1 a decode
    # step of 64 rows touches 34-39 of 64 held experts by the seed, at
    # 0.02 51-53 and at zero 53-54 (PERF.md section 6, PR 44)
    moe_choice_bias_std: Optional[float] = None
    # how ``norm_topk_prob`` guards its division: the chosen scores' sum
    # plus this (``moe_gate_norm_added``: lfm2's ``sum + 1e-6``) or the
    # larger of the two (every older family).  None: 1e-20 under a
    # sigmoid router and 1e-9 under a softmax, what those families run
    moe_gate_norm_eps: Optional[float] = None
    moe_gate_norm_added: bool = False
    # the chosen gates (after ``norm_topk_prob``) times this
    moe_routed_scale: float = 1.0
    # shared experts: ONE MLP of ``moe_shared_experts`` times an expert's
    # width (gated where the experts are) that every token passes
    # through, not weighted by the router, added to the routed sum.
    # 0: none
    moe_shared_experts: int = 0
    # the shared MLP's output times ``sigmoid(x w_s)``, ONE gate a token
    # (``w_s`` [hidden, 1], a leaf of the ``shared`` subtree; qwen3_next's
    # ``shared_expert_gate``), in float32
    moe_shared_expert_gate: bool = False
    # the first layers of a sparse model that keep a dense MLP of
    # ``ffn_hidden_size`` (``first_k_dense_replace``); their parameters
    # are stacked apart from the sparse layers' (``dense_layers``)
    moe_first_dense_layers: int = 0
    # ONE CHIP'S SHARE of a layer whose experts are spread over several:
    # the router scores ``moe_router_experts`` (None: ``num_experts``)
    # and the layer holds the ``num_experts`` contiguous ones from
    # ``moe_experts_first`` on (``params['experts']`` is ``[num_experts,
    # ...]``).  A choice that falls on an expert held elsewhere is
    # computed elsewhere: here it is routed nowhere and its gate, which
    # was normalised over ALL the token's choices, is dropped with it
    # (models/moe.py).  Inference only
    moe_router_experts: Optional[int] = None
    moe_experts_first: int = 0

    # Mamba-2 state-space mixers (the 'mamba' layer type; models/mamba.py):
    # an inner width of ``mamba_n_heads * mamba_d_head``, one state of
    # ``[mamba_d_head, mamba_d_state]`` a head, ``mamba_n_groups`` groups
    # of heads sharing B and C, a causal depthwise convolution of
    # ``mamba_d_conv`` taps over x, B and C; ``mamba_chunk_size`` is the
    # chunked scan's block (an algorithm's parameter: no result changes)
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    # gated short-convolution mixers (the 'conv' layer type;
    # models/short_conv.py): a causal depthwise convolution of
    # ``conv_taps`` taps a channel over ``B * X``, the hidden width wide,
    # with a bias a channel or none.  Fields of their own: a stack could
    # set ``mamba_d_conv`` beside them
    conv_taps: int = 3
    conv_mixer_bias: bool = False
    # gated delta-rule mixers (the 'gated_delta' layer type;
    # models/gated_delta.py): ``delta_key_heads`` query/key heads of
    # ``delta_key_dim`` serving ``delta_value_heads`` value heads of
    # ``delta_value_dim`` (key head j serves value heads j * r .. j * r +
    # r - 1, r their ratio), one state ``[delta_key_dim,
    # delta_value_dim]`` a value head, a causal depthwise convolution of
    # ``delta_conv_taps`` taps over q, k and v with no bias
    delta_key_heads: int = 16
    delta_value_heads: int = 32
    delta_key_dim: int = 128
    delta_value_dim: int = 128
    delta_conv_taps: int = 4
    # muP-style multipliers (Granite): attention scores times this in
    # place of 1/sqrt(head_dim) (None: 1/sqrt(head_dim)); both residual
    # branches times ``residual_multiplier``; the logits divided by
    # ``logits_scaling``.  ``embedding_multiplier`` is further down
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # latent attention (DeepSeek's MLA; on when ``kv_lora_rank`` is set):
    # keys and values are expanded from ONE latent of ``kv_lora_rank`` a
    # token (RMSNorm'd, its own scale) and one rotary key head of
    # ``qk_rope_head_dim`` shared by every query head; a query head is
    # ``qk_nope_head_dim + qk_rope_head_dim`` wide (only the rotary part
    # rotates), a value head ``v_head_dim``.  The paged cache holds the
    # latent and the rotary key, not heads (``ops/paged_kv.py``).
    # ``q_lora_rank`` (a compressed query): the query's projection is
    # two, h -> ``q_lora_rank`` and, after an RMSNorm of its own, ->
    # heads x (nope + rope).  Group-limited routing (``moe_n_group`` /
    # ``moe_topk_group``) is named so that a published config that sets
    # it is refused by name
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    moe_n_group: int = 1
    moe_topk_group: int = 1

    # RMSNorm on the query and key projections before the rotary
    # embedding, over the WHOLE projection (all heads together) with a
    # learned scale of the projection's width (OLMoE's q_norm / k_norm)
    qk_norm: bool = False
    # the per-head form: each head's head_dim values are normalised by
    # themselves, with ONE learned scale of head_dim a layer for the
    # queries and one for the keys (Qwen3's and Keye's q_norm / k_norm)
    qk_norm_per_head: bool = False

    # learned sparse attention (DeepSeek Sparse Attention, Keye's
    # ``sa_config``): a small indexer of ``dsa_index_heads`` heads of
    # ``dsa_index_head_dim`` and ONE key head scores every earlier
    # position for each query, and the query attends only the
    # ``dsa_topk`` best (every position while there are no more than
    # that).  0 heads: no indexer, every key attended.  There is no
    # switch that leaves the indexer in and the selection out.
    dsa_index_heads: int = 0
    dsa_index_head_dim: int = 64
    dsa_topk: int = 2048
    # how many of an indexer head's (and of its key's) first dimensions
    # rotate, the rest passing as they are (None: all of them), and what
    # the indexer's QUERY projection reads: the layer's normed ``input``,
    # or the ``compressed`` query (``q_lora_rank``'s normed output)
    dsa_index_rope_dim: Optional[int] = None
    dsa_index_query: str = "input"
    # rotary frequency pairs dealt to several position streams in
    # sections (``mrope_section``: temporal, height, width); a text
    # token's positions coincide and the embedding is the plain one
    rope_sections: Optional[Tuple[int, ...]] = None

    # QKV-projection-only bias (Qwen2-style: attention in-projections
    # carry biases while every other linear is bias-free)
    add_qkv_bias: bool = False
    # scale the word-embedding output by this factor (Gemma and afmoe's
    # ``mup_enabled`` multiply by sqrt(hidden_size), Granite by a
    # published number; a tied LM head uses the UNSCALED table)
    embedding_multiplier: Optional[float] = None
    # fraction of each head's dims that rotate (GPT-NeoX/Pythia
    # rotary_pct; 1.0 = full rotary)
    rotary_percent: float = 1.0

    # --- context parallelism algorithm (TPU-native extension; the
    # reference has neither): "ring" = K/V ppermute around the cp axis
    # (parallel/ring_attention.py, any head count); "ulysses" = all-to-all
    # heads<->sequence so attention runs dense+local with the tuned flash
    # kernel (parallel/ulysses.py; needs heads % cp == 0, auto-falls back
    # to ring otherwise). ---
    context_parallel_algo: str = "ring"

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            object.__setattr__(self, "ffn_hidden_size", 4 * self.hidden_size)
        if self.kv_channels is None:
            object.__setattr__(
                self, "kv_channels", self.hidden_size // self.num_attention_heads
            )
        if self.num_attention_heads_kv is None:
            object.__setattr__(
                self, "num_attention_heads_kv", self.num_attention_heads
            )
        if self.max_position_embeddings is None:
            object.__setattr__(self, "max_position_embeddings", self.seq_length)
        if isinstance(self.position_embedding_type, str):
            object.__setattr__(
                self,
                "position_embedding_type",
                PositionEmbeddingType(self.position_embedding_type),
            )
        if self.context_parallel_algo not in ("ring", "ulysses", "zigzag"):
            raise ValueError(
                f"context_parallel_algo must be ring|ulysses|zigzag, got "
                f"{self.context_parallel_algo!r}")
        if self.dsa_index_heads > 0 and (
                self.dsa_topk < 1 or self.dsa_index_head_dim % 2):
            raise ValueError(
                f"sparse attention needs dsa_topk >= 1 and an even "
                f"dsa_index_head_dim, got {self.dsa_topk} and "
                f"{self.dsa_index_head_dim}")
        if self.layer_types is not None:
            types = tuple(str(t) for t in self.layer_types)
            object.__setattr__(self, "layer_types", types)
            if not types or set(types) - set(LAYER_TYPES):
                raise ValueError(f"layer_types are one period of "
                                 f"{'|'.join(LAYER_TYPES)}, got {types!r}")
            if self.num_layers % len(types):
                raise ValueError(
                    f"num_layers ({self.num_layers}) must be whole periods "
                    f"of the {len(types)} layer_types")
            if "moe" in types and self.num_experts <= 1:
                raise ValueError("a 'moe' layer type needs num_experts > 1")
            if "sliding" in types and self.sliding_window_size is None:
                raise ValueError("a 'sliding' layer type needs "
                                 "sliding_window_size")
            if "mamba" in types and (
                    self.mamba_n_heads % self.mamba_n_groups or min(
                        self.mamba_n_heads, self.mamba_d_head,
                        self.mamba_d_state, self.mamba_n_groups,
                        self.mamba_chunk_size) < 1 or self.mamba_d_conv < 2):
                raise ValueError(
                    "state-space layers need positive mamba sizes, "
                    "mamba_d_conv >= 2 and whole groups of heads")
            if "conv" in types and self.conv_taps < 2:
                raise ValueError("gated short-convolution layers need "
                                 "conv_taps >= 2 (a column to carry)")
            if "retention" in types and self.head_dim % 8:
                raise ValueError("power-retention layers need a head_dim "
                                 "of whole sublanes (a multiple of 8)")
            if "gated_delta" in types and (
                    min(self.delta_key_heads, self.delta_value_heads,
                        self.delta_key_dim, self.delta_value_dim) < 1
                    or self.delta_value_heads % self.delta_key_heads
                    or self.delta_conv_taps < 2):
                raise ValueError(
                    "gated delta-rule layers need positive delta sizes, "
                    "delta_conv_taps >= 2 and whole key heads of value "
                    "heads")
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps ({self.loop_steps}) is the times "
                             "the stack runs: at least 1")
        if self.early_exit_threshold != 1.0:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold} is not "
                "implemented: only 1.0, every token running every pass, is "
                "(a token that leaves at an earlier pass writes no keys "
                "into the later passes' planes, which the tokens after it "
                "would read)")
        if self.moe_router_experts is not None or self.moe_experts_first:
            routed = self.moe_router_experts or self.num_experts
            if self.num_experts <= 1 or not (
                    0 <= self.moe_experts_first
                    and self.moe_experts_first + self.num_experts <= routed):
                raise ValueError(
                    f"the experts held (num_experts={self.num_experts} "
                    f"from moe_experts_first={self.moe_experts_first}) must "
                    f"lie among the moe_router_experts={routed} the router "
                    "scores")
        if self.rope_yarn_scaling is not None:
            f, orig, fast, slow, att = self.rope_yarn_scaling
            object.__setattr__(self, "rope_yarn_scaling", (
                float(f), int(orig), float(fast), float(slow), float(att)))
        if self.rope_yarn_layer_types is not None:
            object.__setattr__(self, "rope_yarn_layer_types", tuple(
                str(t) for t in self.rope_yarn_layer_types))
            if self.layer_types is None or (
                    set(self.rope_yarn_layer_types) - set(self.layer_types)):
                raise ValueError("rope_yarn_layer_types names types of "
                                 "layer_types")
        if self.rope_layer_types is not None:
            object.__setattr__(self, "rope_layer_types", tuple(
                str(t) for t in self.rope_layer_types))
            if self.layer_types is None or (
                    set(self.rope_layer_types) - set(self.layer_types)):
                raise ValueError("rope_layer_types names types of "
                                 "layer_types")
        if self.rope_sections is not None:
            object.__setattr__(self, "rope_sections",
                               tuple(int(x) for x in self.rope_sections))
        if self.q_lora_rank is not None and (
                self.kv_lora_rank is None or self.q_lora_rank < 1):
            raise ValueError("q_lora_rank (a compressed query) is latent "
                             "attention's: it needs kv_lora_rank and a "
                             "positive width")
        if self.dsa_index_query not in ("input", "compressed") or (
                self.dsa_index_query == "compressed"
                and self.q_lora_rank is None):
            raise ValueError(
                f"dsa_index_query is input|compressed, and compressed "
                f"needs q_lora_rank, got {self.dsa_index_query!r} with "
                f"q_lora_rank={self.q_lora_rank}")
        if self.dsa_index_rope_dim is not None and not (
                0 < self.dsa_index_rope_dim <= self.dsa_index_head_dim
                and self.dsa_index_rope_dim % 2 == 0):
            raise ValueError(
                f"dsa_index_rope_dim ({self.dsa_index_rope_dim}) is even "
                f"and within dsa_index_head_dim "
                f"({self.dsa_index_head_dim})")
        if self.moe_n_group != 1 or self.moe_topk_group != 1:
            raise ValueError("group-limited routing (moe_n_group / "
                             "moe_topk_group other than 1) is not "
                             "implemented")
        if self.mlp_activation not in ("gelu", "relu2") or (
                self.mlp_activation != "gelu" and self.glu_activation):
            raise ValueError(
                f"mlp_activation is gelu|relu2 and ungated (no "
                f"glu_activation), got {self.mlp_activation!r} with "
                f"glu_activation={self.glu_activation!r}")
        if self.moe_score_function not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_score_function must be softmax|sigmoid, got "
                f"{self.moe_score_function!r}")
        if self.moe_gate_norm_eps is None:
            object.__setattr__(
                self, "moe_gate_norm_eps",
                1e-20 if self.moe_score_function == "sigmoid" else 1e-9)
        if self.num_experts <= 1 and (
                self.moe_shared_experts or self.moe_first_dense_layers
                or self.moe_choice_bias):
            raise ValueError("moe_shared_experts, moe_first_dense_layers and "
                             "moe_choice_bias need num_experts > 1")
        if self.moe_shared_expert_gate and not self.moe_shared_experts:
            raise ValueError("moe_shared_expert_gate gates the shared MLP: "
                             "it needs moe_shared_experts > 0")
        if self.moe_first_dense_layers and not (
                0 < self.moe_first_dense_layers < self.num_layers):
            raise ValueError(
                f"moe_first_dense_layers ({self.moe_first_dense_layers}) "
                f"must leave a sparse layer of {self.num_layers}")
        if self.kv_lora_rank is not None and (
                self.qk_rope_head_dim % 2 or min(
                    self.kv_lora_rank, self.qk_nope_head_dim,
                    self.qk_rope_head_dim, self.v_head_dim) < 1):
            raise ValueError("latent attention needs positive widths "
                             "and an even qk_rope_head_dim")
        if self.num_experts > 1:
            if not (1 <= self.moe_top_k <= self.routed_experts):
                raise ValueError(
                    f"moe_top_k ({self.moe_top_k}) must be in [1, "
                    f"{self.routed_experts}], the experts the router scores")
            if self.moe_expert_axis not in ("auto", "expert", "replicated"):
                raise ValueError(
                    f"moe_expert_axis must be auto|expert|replicated, got "
                    f"{self.moe_expert_axis!r}")
        # what this model's mechanisms are not made to work with each
        # other: the table's squares with no feature of the runtime on
        said = refusal(self)
        if said:
            raise ValueError(said)

    # convenience ------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.kv_channels

    @property
    def cache_layers(self) -> int:
        """How many cache planes (a layer's keys and values, or whatever
        its layer keeps) a token holds: one a layer a PASS.  The one place
        that says so: whoever sizes a cache reads this, not
        ``num_layers``."""
        return self.num_layers * self.loop_steps

    @property
    def expert_hidden_size(self) -> int:
        return self.moe_ffn_hidden_size or self.ffn_hidden_size

    @property
    def num_sparse_layers(self) -> int:
        """The layers with experts: all of a sparse model's but its
        leading dense ones, or the 'moe' layers of a stack of one
        sublayer a layer; 0 for a dense model."""
        if self.num_experts <= 1:
            return 0
        if self.one_sublayer:
            return self.mixer_counts["moe"]
        return self.num_layers - self.moe_first_dense_layers

    @property
    def routed_experts(self) -> int:
        """The experts the router scores: all the layer's, of which this
        chip may hold a share (``moe_router_experts``)."""
        return self.moe_router_experts or self.num_experts

    @property
    def holds_a_share(self) -> bool:
        """Whether the layer holds a share of the experts its router
        scores, and not all of them."""
        return self.routed_experts != self.num_experts

    @property
    def state_space(self) -> bool:
        """Whether some layer's mixer is a state-space one."""
        return self.layer_types is not None and "mamba" in self.layer_types

    @property
    def short_conv(self) -> bool:
        """Whether some layer's mixer is a gated short convolution."""
        return self.layer_types is not None and "conv" in self.layer_types

    @property
    def retention(self) -> bool:
        """Whether some layer's mixer is a power retention."""
        return (self.layer_types is not None
                and "retention" in self.layer_types)

    @property
    def gated_delta(self) -> bool:
        """Whether some layer's mixer is a gated delta rule."""
        return (self.layer_types is not None
                and "gated_delta" in self.layer_types)

    @property
    def delta_conv_dim(self) -> int:
        """The channels a delta-rule layer's convolution runs over: its
        queries, keys and values side by side."""
        return (2 * self.delta_key_heads * self.delta_key_dim
                + self.delta_value_heads * self.delta_value_dim)

    @property
    def retention_phi_rows(self) -> int:
        """Rows of ``phi``, the map with ``phi(x) . phi(y) = (x . y)^2``,
        in the layout ``models/retention.py`` chose: ``head_dim / 2 + 1``
        rotations of ``head_dim`` products each (8,320 at 128, where the
        least a symmetric square takes is 8,256)."""
        return (self.head_dim // 2 + 1) * self.head_dim

    @staticmethod
    def state_layer(layer_type: Optional[str]) -> bool:
        """Whether a layer of ``layer_type`` carries a STATE a request
        (arrays a slot, no pages): a 'mamba', a 'conv', a 'retention' or
        a 'gated_delta' mixer."""
        return layer_type in STATE_TYPES

    @property
    def mixers_by_kind(self) -> bool:
        """Whether the stack's mixers are of several kinds, with other
        leaves each, and so stacked apart by kind (``mixer_counts``)."""
        return (self.state_space or self.short_conv or self.retention
                or self.gated_delta or self.one_sublayer)

    @property
    def one_sublayer(self) -> bool:
        """Whether a layer is ONE sublayer under one norm (a mixer or an
        expert layer alone): a stack with 'moe' among its layer types."""
        return self.layer_types is not None and "moe" in self.layer_types

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """The channels the convolution runs over: x, B and C."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def mixer_counts(self) -> dict:
        """How many layers there are of each kind ('mamba', 'conv',
        'retention', 'gated_delta', 'attention', and the expert layers
        'moe' of a stack of one sublayer a layer) in a stack whose kinds'
        parameters are stacked apart; empty for a stack whose layers all hold the same leaves.
        EVERY layer of the depth counts, a sparse model's leading dense
        layers among them: only their MLP is stacked apart
        (``dense_layers``), their mixer is a member of its kind's stack
        by its index in the whole depth."""
        if not self.mixers_by_kind:
            return {}
        reps = self.num_layers // len(self.layer_types)
        return {k: reps * self.layer_types.count(k)
                for k in ("mamba", "conv", "retention", "gated_delta",
                          "attention", "moe")
                if k in self.layer_types}

    def layer_type(self, layer: int) -> Optional[str]:
        """The type of layer ``layer`` of the depth (None for a stack of
        one type)."""
        return self.layer_period[layer % len(self.layer_period)]

    def mixer_index(self, layer: int) -> tuple:
        """(kind, index among the layers of that kind) of layer
        ``layer`` of a stack whose kinds are stacked apart."""
        P = len(self.layer_types)
        kind = self.layer_types[layer % P]
        return kind, ((layer // P) * self.layer_types.count(kind)
                      + self.layer_types[:layer % P].count(kind))

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def qk_head_dim(self) -> int:
        """A query (and expanded key) head's width under latent
        attention: what the scores are scaled by."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def layer_period(self) -> Tuple[Optional[str], ...]:
        """One period of the stack's layer types: layer i is of type
        ``layer_period[i % len(layer_period)]``; ``(None,)`` for a stack
        of one type."""
        return self.layer_types or (None,)

    def attention_of(self, layer_type: Optional[str]):
        """(window, YaRN scaling) of a layer of ``layer_type``, each None
        where the type has none: what ``attention()`` computes by.  For a
        stack of one type (``layer_type`` None) these are the config's
        own fields."""
        if self.layer_types is None:
            return self.sliding_window_size, self.rope_yarn_scaling
        if layer_type not in self.layer_types:
            raise ValueError(
                f"a model with layer_types {self.layer_types} was run "
                f"through a path that gives its layers no type "
                f"(got {layer_type!r})")
        yarn_on = self.rope_yarn_layer_types
        return (self.sliding_window_size if layer_type == "sliding" else None,
                self.rope_yarn_scaling
                if yarn_on is None or layer_type in yarn_on else None)

    def rotates(self, layer_type: Optional[str]) -> bool:
        """Whether a layer of ``layer_type`` rotates its queries and keys
        (``rope_layer_types``; with none named, every type does).  Beside
        ``attention_of`` and not a third value of it: the benchmark's
        accepted files compare that method's result with a PAIR."""
        return (self.rope_layer_types is None
                or layer_type in self.rope_layer_types)

    @property
    def num_query_groups(self) -> int:
        return self.num_attention_heads_kv

    @property
    def params_jnp_dtype(self):
        return DTYPES[self.params_dtype]

    @property
    def compute_jnp_dtype(self):
        return DTYPES[self.compute_dtype]

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class TrainConfig:
    """Optimization / schedule configuration (reference: _add_training_args,
    _add_learning_rate_args, _add_mixed_precision_args in arguments.py)."""

    micro_batch_size: int = 1
    global_batch_size: int = 1
    rampup_batch_size: Optional[Tuple[int, int, int]] = None  # (start, incr, samples)
    train_iters: int = 0
    # optimizer
    optimizer: str = "adam"             # 'adam' | 'sgd'
    lr: float = 1e-4
    min_lr: float = 0.0
    lr_decay_style: str = "linear"      # constant|linear|cosine|inverse-square-root
    lr_decay_iters: Optional[int] = None
    lr_warmup_iters: int = 0
    lr_warmup_fraction: Optional[float] = None
    weight_decay: float = 0.01
    start_weight_decay: Optional[float] = None
    end_weight_decay: Optional[float] = None
    weight_decay_incr_style: str = "constant"
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    sgd_momentum: float = 0.9
    # 'fp32' (default) | 'bf16': storage dtype of the Adam moments /
    # SGD momentum buffer.  bf16 halves optimizer-state HBM (and its
    # read+write traffic in the step, and checkpoint size); the step
    # math still runs in fp32 (state is upcast, computed, downcast).
    # Master params are unaffected — they stay fp32.  Beyond-reference
    # (the reference's apex Adam is fp32-state only).
    optimizer_state_dtype: str = "fp32"
    clip_grad: float = 1.0
    # mixed precision
    fp16: bool = False
    bf16: bool = False
    loss_scale: Optional[float] = None          # static scale; None -> dynamic
    initial_loss_scale: float = 2.0 ** 32
    min_loss_scale: float = 1.0
    loss_scale_window: int = 1000
    hysteresis: int = 2
    # misc
    seed: int = 1234
    data_parallel_random_init: bool = False

    def __post_init__(self):
        if self.optimizer_state_dtype not in ("fp32", "bf16"):
            raise ValueError(
                f"optimizer_state_dtype must be fp32|bf16, got "
                f"{self.optimizer_state_dtype!r}")

    @property
    def grad_accum_steps_fn(self):
        return None
