#!/usr/bin/env python
"""Pretrain / finetune / instruct-tune GPT-family models on TPU.

Reference: ``/root/reference/finetune.py`` — the fork's primary entry
point: ``--model_name={gpt,llama,llama2,codellama,falcon,mistral,mixtral,olmoe,keye,mellum,kanana,glm5,trinity,nemotron_h,lfm2,brumby,qwen3_next,ouro,qwen2}``
selects architecture defaults, data comes from packed GPT or instruction
datasets, and the loop runs under 3-way parallelism.

Usage mirrors the reference (``docs/guide/getting_started.md``):

    python finetune.py --model_name=llama2 \
        --tensor_model_parallel_size=8 --pipeline_model_parallel_size=1 \
        --data_path=/data/corpus --tokenizer_type=SentencePieceTokenizer \
        --vocab_file=tokenizer.model --bf16 --use_flash_attn \
        --micro_batch_size=2 --global_batch_size=128 --train_iters=1000 \
        --lr=1e-5 --lr_decay_style=cosine --save=ckpts --load=ckpts
"""

from __future__ import annotations

import time

_FIRST_STAMP = time.perf_counter()  # before the imports: tracing's timeline

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu import checkpointing, topology, tracing
from megatron_llm_tpu.data.data_samplers import place_host_batch
from megatron_llm_tpu.arguments import (
    parallel_config_from_args,
    train_config_from_args,
    transformer_config_from_args,
)
from megatron_llm_tpu.dist_signal_handler import DistributedSignalHandler
from megatron_llm_tpu.global_vars import get_counters
from megatron_llm_tpu.initialize import initialize_megatron
from megatron_llm_tpu.models import MODEL_REGISTRY
from megatron_llm_tpu.models.lfm2 import PUBLISHED_LAYER_TYPES
from megatron_llm_tpu.optimizer import (
    MegatronOptimizer,
    OptimizerParamScheduler,
)
from megatron_llm_tpu.optimizer.optimizer import map_param_trees
from megatron_llm_tpu.parallel import glu_pairs, sharding as sh
from megatron_llm_tpu.training import pretrain
from jax.sharding import NamedSharding, PartitionSpec as P

tracing.startup_completed("imports", _FIRST_STAMP, time.perf_counter())

MODEL_DEFAULTS = {
    # reference: finetune.py model_provider asserts + weights tables
    "llama": dict(position_embedding_type="rotary", glu_activation="swiglu",
                  use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                  hidden_dropout=0.0, attention_dropout=0.0),
    "llama2": dict(position_embedding_type="rotary", glu_activation="swiglu",
                   use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                   hidden_dropout=0.0, attention_dropout=0.0),
    "llama3": dict(position_embedding_type="rotary", glu_activation="swiglu",
                   use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                   rope_theta=500000.0,
                   hidden_dropout=0.0, attention_dropout=0.0),
    "codellama": dict(position_embedding_type="rotary", glu_activation="swiglu",
                      use_rms_norm=True, use_bias=False,
                      tie_embed_logits=False, rope_theta=1e6,
                      hidden_dropout=0.0, attention_dropout=0.0),
    "falcon": dict(position_embedding_type="rotary", parallel_attn=True,
                   use_bias=False, hidden_dropout=0.0, attention_dropout=0.0),
    "mistral": dict(position_embedding_type="rotary", glu_activation="swiglu",
                    use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                    sliding_window_size=4096,
                    hidden_dropout=0.0, attention_dropout=0.0),
    # sparse-MoE mistral (TPU-native extension; the reference has no MoE)
    "mixtral": dict(position_embedding_type="rotary", glu_activation="swiglu",
                    use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                    num_experts=8, moe_top_k=2, rope_theta=1e6,
                    hidden_dropout=0.0, attention_dropout=0.0),
    # 64 small experts, 8 a token, gates as the router gives them, QK-norm
    "olmoe": dict(position_embedding_type="rotary", glu_activation="swiglu",
                  use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                  num_experts=64, moe_top_k=8, norm_topk_prob=0,
                  qk_norm=True, rope_theta=10000.0,
                  hidden_dropout=0.0, attention_dropout=0.0),
    # Keye-VL-2.0's language model: 128 small experts, 8 a token with
    # gates renormalised, per-head QK-norm, a sparse-attention indexer
    "keye": dict(position_embedding_type="rotary", glu_activation="swiglu",
                 use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                 num_experts=128, moe_top_k=8, norm_topk_prob=1,
                 qk_norm_per_head=True, kv_channels=128, rope_theta=1e7,
                 layernorm_epsilon=1e-6, rope_sections=[16, 24, 24],
                 dsa_index_heads=16, dsa_index_head_dim=64, dsa_topk=2048,
                 hidden_dropout=0.0, attention_dropout=0.0),
    # Mellum 2: three sliding-window layers of 1,024 to each full layer,
    # YaRN on the full layers only; 64 small experts, 8 a token
    "mellum": dict(position_embedding_type="rotary", glu_activation="swiglu",
                   use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                   num_experts=64, moe_top_k=8, norm_topk_prob=1,
                   kv_channels=128, rope_theta=500000.0,
                   layernorm_epsilon=1e-6, sliding_window_size=1024,
                   layer_types=["sliding", "sliding", "sliding", "full"],
                   rope_yarn_scaling=[16.0, 8192.0, 32.0, 1.0,
                                      1.2772588722239782],
                   rope_yarn_layer_types=["full"],
                   hidden_dropout=0.0, attention_dropout=0.0),
    # kanana-2 (model_type deepseek_v3): latent attention, a sigmoid
    # router with a choice bias and a scale, two shared experts, one
    # leading dense layer
    "kanana": dict(position_embedding_type="rotary", glu_activation="swiglu",
                   use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                   num_experts=128, moe_top_k=6, norm_topk_prob=1,
                   moe_score_function="sigmoid", moe_choice_bias=1,
                   moe_routed_scale=2.448, moe_shared_experts=2,
                   moe_first_dense_layers=1, kv_lora_rank=512,
                   qk_nope_head_dim=128, qk_rope_head_dim=64,
                   v_head_dim=128, rope_theta=1e6, layernorm_epsilon=1e-6,
                   hidden_dropout=0.0, attention_dropout=0.0),
    # GLM-5 (model_type glm_moe_dsa): kanana's layer behind a compressed
    # query, with keye's indexer choosing each query's latents (its
    # queries from the compressed query, half of its head rotating),
    # three leading dense layers, one shared expert
    "glm5": dict(position_embedding_type="rotary", glu_activation="swiglu",
                 use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                 num_experts=256, moe_top_k=8, norm_topk_prob=1,
                 moe_score_function="sigmoid", moe_choice_bias=1,
                 moe_routed_scale=2.5, moe_shared_experts=1,
                 moe_first_dense_layers=3, kv_lora_rank=512,
                 q_lora_rank=2048, qk_nope_head_dim=192,
                 qk_rope_head_dim=64, v_head_dim=256, dsa_index_heads=32,
                 dsa_index_head_dim=128, dsa_index_rope_dim=64,
                 dsa_index_query="compressed", dsa_topk=2048,
                 rope_theta=1e6, layernorm_epsilon=1e-5,
                 hidden_dropout=0.0, attention_dropout=0.0),
    # Trinity-Mini (model_type afmoe): a gate on the attention output,
    # four norms a layer, window layers that rotate beside full layers
    # that carry no positions, two leading dense layers inside the typed
    # stack, Kanana's router form with one shared expert
    "trinity": dict(position_embedding_type="rotary", glu_activation="swiglu",
                    use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                    num_experts=128, moe_top_k=8, norm_topk_prob=1,
                    moe_score_function="sigmoid", moe_choice_bias=1,
                    moe_routed_scale=2.826, moe_shared_experts=1,
                    moe_first_dense_layers=2, qk_norm_per_head=True,
                    attention_output_gate=True, sublayer_output_norm=True,
                    kv_channels=128, rope_theta=10000.0,
                    layernorm_epsilon=1e-5, sliding_window_size=2048,
                    layer_types=["sliding", "sliding", "sliding", "full"],
                    rope_layer_types=["sliding"],
                    hidden_dropout=0.0, attention_dropout=0.0),
    # granite-4.0-h-small (model_type granitemoehybrid): nine Mamba-2
    # mixers to each attention layer with no position embedding, 72
    # experts with a shared MLP, four multipliers, a tied head
    "granite": dict(position_embedding_type="none", glu_activation="swiglu",
                    use_rms_norm=True, use_bias=False, tie_embed_logits=True,
                    num_experts=72, moe_top_k=10, norm_topk_prob=1,
                    moe_shared_experts=2, kv_channels=128,
                    layer_types=["mamba"] * 5 + ["attention"]
                    + ["mamba"] * 4,
                    attention_multiplier=0.0078125, embedding_multiplier=12.0,
                    residual_multiplier=0.22, logits_scaling=16.0,
                    layernorm_epsilon=1e-5,
                    hidden_dropout=0.0, attention_dropout=0.0),
    # nemotron-3-nano (model_type nemotron_h): 52 layers of ONE sublayer
    # each by the published pattern (M a Mamba-2 mixer of eight groups,
    # * attention with no position embedding, E ungated relu^2 experts
    # under a sigmoid router with a shared MLP), an untied head
    "nemotron_h": dict(position_embedding_type="none", mlp_activation="relu2",
                       use_rms_norm=True, use_bias=False,
                       tie_embed_logits=False, num_experts=128, moe_top_k=6,
                       norm_topk_prob=1, moe_score_function="sigmoid",
                       moe_choice_bias=1, moe_routed_scale=2.5,
                       moe_shared_experts=2, kv_channels=128,
                       hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*E"
                       "MEMEM*EMEMEMEM*EMEMEMEME",
                       mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
                       mamba_n_groups=8, mamba_chunk_size=128,
                       layernorm_epsilon=1e-5,
                       hidden_dropout=0.0, attention_dropout=0.0),
    # LFM2-8B-A1B (model_type lfm2_moe): gated short convolutions of
    # three taps beside rotating attention of 64-wide heads by a pattern
    # that does not repeat, two leading dense layers, 32 experts under a
    # sigmoid router whose gates are divided by their sum + 1e-6, a tied
    # head
    "lfm2": dict(position_embedding_type="rotary", glu_activation="swiglu",
                 use_rms_norm=True, use_bias=False, tie_embed_logits=True,
                 num_experts=32, moe_top_k=4, norm_topk_prob=1,
                 moe_score_function="sigmoid", moe_choice_bias=1,
                 moe_routed_scale=1.0, moe_gate_norm_eps=1e-6,
                 moe_gate_norm_added=1, moe_first_dense_layers=2,
                 qk_norm_per_head=True, kv_channels=64, rope_theta=1e6,
                 conv_taps=3, conv_mixer_bias=0, layernorm_epsilon=1e-5,
                 layer_types=list(PUBLISHED_LAYER_TYPES),
                 hidden_dropout=0.0, attention_dropout=0.0),
    # Brumby-14B-Base (model_type brumby): a Qwen3-14B-shaped trunk whose
    # every layer's mixer is a power retention of degree 2 (no key and no
    # value kept: a recurrent state a key-value head), per-head QK-norm,
    # no bias, an untied head
    "brumby": dict(position_embedding_type="rotary", glu_activation="swiglu",
                   use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                   qk_norm_per_head=True, kv_channels=128, rope_theta=1e6,
                   layer_types=["retention"],
                   layernorm_epsilon=1e-6,
                   hidden_dropout=0.0, attention_dropout=0.0),
    # Qwen3-Next-80B-A3B (model_type qwen3_next): three gated delta-rule
    # layers to every gated attention layer of 256-wide heads that rotate
    # in their first quarter, 512 experts chosen ten a token by a softmax
    # router beside a shared expert under its own sigmoid gate, an untied
    # head
    "qwen3_next": dict(position_embedding_type="rotary",
                       glu_activation="swiglu", use_rms_norm=True,
                       use_bias=False, tie_embed_logits=False,
                       num_experts=512, moe_top_k=10, norm_topk_prob=1,
                       moe_shared_experts=1, moe_shared_expert_gate=True,
                       qk_norm_per_head=True, attention_output_gate=True,
                       kv_channels=256, rotary_percent=0.25, rope_theta=1e7,
                       layer_types=["gated_delta", "gated_delta",
                                    "gated_delta", "attention"],
                       layernorm_epsilon=1e-6,
                       hidden_dropout=0.0, attention_dropout=0.0),
    # Ouro-2.6B (model_type ouro): a llama-style stack of four norms a
    # layer that runs ``loop_steps`` times over the same weights, the
    # final norm after each pass and cache planes of its own a pass
    "ouro": dict(position_embedding_type="rotary", glu_activation="swiglu",
                 use_rms_norm=True, use_bias=False, tie_embed_logits=False,
                 sublayer_output_norm=True, loop_steps=4, kv_channels=128,
                 rope_theta=1e6, layernorm_epsilon=1e-6,
                 hidden_dropout=0.0, attention_dropout=0.0),
    "qwen2": dict(position_embedding_type="rotary", glu_activation="swiglu",
                  use_rms_norm=True, use_bias=False, add_qkv_bias=True,
                  tie_embed_logits=False, rope_theta=1e6,
                  hidden_dropout=0.0, attention_dropout=0.0),
    "gemma": dict(position_embedding_type="rotary", glu_activation="geglu",
                  use_rms_norm=True, use_bias=False, layernorm_epsilon=1e-6,
                  hidden_dropout=0.0, attention_dropout=0.0),
    "gpt_neox": dict(position_embedding_type="rotary", use_bias=True,
                     parallel_attn=True, parallel_layernorm=True,
                     rotary_percent=0.25, tie_embed_logits=False,
                     gelu_variant="exact",
                     hidden_dropout=0.0, attention_dropout=0.0),
    "pythia": dict(position_embedding_type="rotary", use_bias=True,
                   parallel_attn=True, parallel_layernorm=True,
                   rotary_percent=0.25, tie_embed_logits=False,
                   gelu_variant="exact",
                   hidden_dropout=0.0, attention_dropout=0.0),
    "gpt": dict(),
}


def extra_args(parser):
    g = parser.add_argument_group("finetune")
    g.add_argument("--model_name", required=True,
                   choices=sorted(MODEL_DEFAULTS))
    g.add_argument("--model_type", default=None)  # compat
    # LoRA (megatron_llm_tpu/lora.py): train low-rank adapters over a
    # frozen base — Adam state and grads shrink to the adapter size.
    # Checkpoints are exported MERGED (standard format); continuing a
    # LoRA run means re-finetuning from the merged weights.
    g.add_argument("--lora_rank", type=int, default=0,
                   help="enable LoRA with this rank (0 = off)")
    g.add_argument("--lora_alpha", type=float, default=None,
                   help="LoRA scaling numerator (default 2*rank)")
    g.add_argument("--lora_targets",
                   default="query_key_value,dense",
                   help="comma-separated linear names to adapt")
    return parser


def model_provider(args):
    if args.model_name in ("gemma", "trinity") and \
            getattr(args, "embedding_multiplier", None) is None:
        # gemma's sqrt(hidden) embedding normalizer (and afmoe's
        # mup_enabled) depends on the parsed hidden size, so the static
        # preset table can't carry it
        import math

        args.embedding_multiplier = math.sqrt(args.hidden_size)
    cfg = transformer_config_from_args(args, args.model_name)
    return MODEL_REGISTRY[args.model_name](cfg)


def build_data_iterator(args, mesh, num_micro, consumed_samples=0):
    """Packed GPT or instruction dataset -> global-batch iterator with dp
    sharding applied (reference: build_train_valid_test_data_iterators,
    training.py:877; data only needs loading once per process).

    ``consumed_samples`` (from the checkpoint meta) drives the sampler's
    deterministic skip so an elastic resume — possibly at a different
    dp x slice product — continues the same global sample order."""
    # total data parallelism: the batch dim spans ('slice', 'dp')
    total_dp = args.data_parallel_size * getattr(args, "num_slices", 1)
    if args.data_path is None:
        # synthetic data (smoke/bench runs)
        rng = np.random.RandomState(args.seed)
        mb = args.micro_batch_size * total_dp

        def synth():
            while True:
                toks = rng.randint(
                    0, args.padded_vocab_size,
                    (num_micro, mb, args.seq_length),
                ).astype(np.int32)
                yield {
                    "tokens": toks,
                    "labels": np.roll(toks, -1, axis=-1),
                    "loss_mask": np.ones_like(toks, np.float32),
                }
        host_iter, eval_iter = synth(), None
    elif args.data_type == "instruction":
        from megatron_llm_tpu.data.data_samplers import (
            build_pretraining_data_loader,
        )
        from megatron_llm_tpu.data.instruction_dataset import (
            InstructionDataset,
            build_instruction_collator,
        )
        from megatron_llm_tpu.global_vars import get_tokenizer

        ds = InstructionDataset(
            args.data_path[0],
            num_samples=args.train_iters * args.global_batch_size,
            seed=args.seed,
        )
        collate = build_instruction_collator(
            args.seq_length, get_tokenizer().pad,
            variable_seq_lengths=args.variable_seq_lengths,
            scalar_loss_mask=args.scalar_loss_mask,
        )
        host_iter = iter(build_pretraining_data_loader(
            ds, consumed_samples, args.micro_batch_size, total_dp,
            num_micro, args.dataloader_type, args.seed, collate_fn=collate,
        ))
        eval_iter = None
    else:
        from megatron_llm_tpu.data.data_samplers import (
            build_pretraining_data_loader,
        )
        from megatron_llm_tpu.data.gpt_dataset import (
            build_train_valid_test_datasets,
        )

        n_train = args.train_iters * args.global_batch_size
        n_eval = args.eval_iters * args.global_batch_size
        train_ds, valid_ds, _ = build_train_valid_test_datasets(
            args.data_path, args.split,
            [n_train, n_eval, 0],
            args.seq_length, args.seed, args.data_impl,
        )
        host_iter = iter(build_pretraining_data_loader(
            train_ds, consumed_samples, args.micro_batch_size, total_dp,
            num_micro, args.dataloader_type, args.seed,
        ))
        eval_iter = (iter(build_pretraining_data_loader(
            valid_ds, 0, args.micro_batch_size, total_dp,
            num_micro, args.dataloader_type, args.seed,
        )) if valid_ds is not None else None)

    dsh = NamedSharding(mesh, P(None, topology.data_axes(), None))

    def shard(it):
        if it is None:
            return None
        def gen():
            for b in it:
                yield {k: place_host_batch(v, dsh) for k, v in b.items()}
        return gen()

    return shard(host_iter), shard(eval_iter)


_INVERTED_FLAGS = {
    "use_bias": "--no_bias",
    "tie_embed_logits": "--no_tie_embed_logits",
}


def _apply_model_defaults(args, argv):
    """Model presets fill any flag the user didn't pass explicitly
    (reference: finetune.py passes args_defaults + the model classes
    assert; here the presets make the CLI self-sufficient)."""
    for k, v in MODEL_DEFAULTS[args.model_name].items():
        flags = [f"--{k}"]
        if k in _INVERTED_FLAGS:
            flags.append(_INVERTED_FLAGS[k])
        explicitly_set = any(
            a == flag or a.startswith(flag + "=")
            for a in argv for flag in flags
        )
        if not explicitly_set:
            setattr(args, k, v)


# checkpoint-args field -> CLI args attribute (reference checkpointing.py
# _set_arg list; config_to_args writes the config-field spellings)
_CKPT_ARG_MAP = {
    "num_layers": "num_layers",
    "hidden_size": "hidden_size",
    "ffn_hidden_size": "ffn_hidden_size",
    "num_attention_heads": "num_attention_heads",
    "num_attention_heads_kv": "num_attention_heads_kv",
    "kv_channels": "kv_channels",
    "seq_length": "seq_length",
    "max_position_embeddings": "max_position_embeddings",
    "padded_vocab_size": "padded_vocab_size",
    "position_embedding_type": "position_embedding_type",
    "glu_activation": "glu_activation",
    "tie_embed_logits": "tie_embed_logits",
    "add_bias_linear": "use_bias",
    "use_post_ln": "use_post_ln",
    "parallel_attn": "parallel_attn",
    "parallel_layernorm": "parallel_layernorm",
    "sliding_window_size": "sliding_window_size",
    "layernorm_epsilon": "layernorm_epsilon",
    "rope_theta": "rope_theta",
    "rope_scaling_factor": "rope_scaling_factor",
    "rope_llama3_scaling": "rope_llama3_scaling",
    # MoE architecture fields: a dense rebuild of an MoE checkpoint (or
    # vice versa) fails orbax restore on the param-tree mismatch
    "num_experts": "num_experts",
    "moe_top_k": "moe_top_k",
    "moe_ffn_hidden_size": "moe_ffn_hidden_size",
    "moe_capacity_factor": "moe_capacity_factor",
    "moe_min_capacity": "moe_min_capacity",
    "norm_topk_prob": "norm_topk_prob",
    # olmoe's QK-norm adds two scale vectors a layer to the param tree
    "qk_norm": "qk_norm",
    # keye's per-head QK-norm and indexer change the param tree too
    "qk_norm_per_head": "qk_norm_per_head",
    "dsa_index_heads": "dsa_index_heads",
    "dsa_index_head_dim": "dsa_index_head_dim",
    "dsa_topk": "dsa_topk",
    "dsa_index_rope_dim": "dsa_index_rope_dim",
    "dsa_index_query": "dsa_index_query",
    "rope_sections": "rope_sections",
    # kanana's router, shared MLP, dense layers and latent attention
    "moe_score_function": "moe_score_function",
    "moe_choice_bias": "moe_choice_bias",
    "moe_routed_scale": "moe_routed_scale",
    "moe_shared_experts": "moe_shared_experts",
    "moe_first_dense_layers": "moe_first_dense_layers",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    # granite's share of experts, state-space sizes and multipliers
    "moe_router_experts": "moe_router_experts",
    "moe_experts_first": "moe_experts_first",
    "mamba_n_heads": "mamba_n_heads",
    "mamba_d_head": "mamba_d_head",
    "mamba_d_state": "mamba_d_state",
    "mamba_n_groups": "mamba_n_groups",
    "mamba_d_conv": "mamba_d_conv",
    "mamba_chunk_size": "mamba_chunk_size",
    "mamba_conv_bias": "mamba_conv_bias",
    # nemotron_h's ungated MLPs
    "mlp_activation": "mlp_activation",
    # lfm2's convolution and its router's normaliser
    "conv_taps": "conv_taps",
    "conv_mixer_bias": "conv_mixer_bias",
    "moe_gate_norm_eps": "moe_gate_norm_eps",
    "moe_gate_norm_added": "moe_gate_norm_added",
    "attention_multiplier": "attention_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    # forward-math fields of a model with a layer type per layer
    "layer_types": "layer_types",
    "rope_yarn_scaling": "rope_yarn_scaling",
    "rope_yarn_layer_types": "rope_yarn_layer_types",
    "rope_layer_types": "rope_layer_types",
    # trinity's gate widens the fused QKV kernel, its output norms add
    # two leaves a layer
    "attention_output_gate": "attention_output_gate",
    "sublayer_output_norm": "sublayer_output_norm",
    # ouro's passes: the exit gate is a leaf, and the passes are forward
    # math
    "loop_steps": "loop_steps",
    # qwen2's QKV-only bias changes the param tree like the MoE fields do
    "add_qkv_bias": "add_qkv_bias",
    # gemma's embedding normalizer changes forward math, not the tree
    "embedding_multiplier": "embedding_multiplier",
    # forward-math fields for the NeoX family
    "rotary_percent": "rotary_percent",
    "gelu_variant": "gelu_variant",
}


def _apply_checkpoint_args(args):
    """--use_checkpoint_args: the architecture recorded in the checkpoint
    overrides the CLI (reference checkpointing.py:520-560)."""
    ckpt_args = checkpointing.load_checkpoint_args(
        args.load, getattr(args, "load_iters", None))
    if not ckpt_args:
        print(" > WARNING: --use_checkpoint_args but the checkpoint "
              "records no args", flush=True)
        return
    for src, dst in _CKPT_ARG_MAP.items():
        # no is-not-None filter: a recorded null is a real override
        # (e.g. glu_activation=None must clear a model preset's swiglu,
        # or the restored MLP shapes mismatch the checkpoint)
        if src in ckpt_args:
            setattr(args, dst, ckpt_args[src])
    if ckpt_args.get("normalization") is not None:
        args.use_rms_norm = ckpt_args["normalization"] == "rmsnorm"
    print(" > using architecture args from the checkpoint", flush=True)


def main():
    args = initialize_megatron(extra_args_provider=extra_args)
    _apply_model_defaults(args, sys.argv[1:])
    if args.use_checkpoint_args and args.load:
        _apply_checkpoint_args(args)
        # re-derive and re-assert everything validate_args computed from
        # the CLI architecture (vpp divisibility, encoder_* backfills...)
        # against the overridden values
        from megatron_llm_tpu.arguments import validate_args
        validate_args(args)
    if args.padded_vocab_size is None:
        raise SystemExit("need --vocab_size/--padded_vocab_size or a tokenizer")

    # hardened checkpoint IO knobs + fault-tolerance runtime
    # (docs/guide/fault_tolerance.md)
    checkpointing.configure_save(
        total_limit=getattr(args, "save_total_limit", 0),
        retries=getattr(args, "save_retries", 2),
        retry_backoff=getattr(args, "save_retry_backoff", 0.25))
    from megatron_llm_tpu.resilience import build_resilience
    resilience = build_resilience(args)

    mesh = topology.get_mesh()
    # built before the checkpoint load so the startup restore lands in
    # the trace (--trace_dir opens a checkpoint_load span)
    from megatron_llm_tpu.telemetry import build_telemetry

    with tracing.startup_span("build_model", model_name=args.model_name):
        model = model_provider(args)
        telemetry = build_telemetry(args, model)
    tc = train_config_from_args(args)
    pc = parallel_config_from_args(args)
    num_micro = args.global_batch_size // (
        args.micro_batch_size * args.data_parallel_size * args.num_slices
    )

    # params: fresh init or checkpoint
    params = None
    start_iteration = 0
    opt_state = None
    consumed_samples = 0
    if args.load:
        t_load = time.perf_counter()
        # abstract template (shapes + current-mesh shardings, no device
        # memory) makes the orbax restore direct-to-device on THIS mesh —
        # i.e. load-time resharding even when the checkpoint was written
        # under a different topology
        try:
            abstract = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(args.seed)))
            shardings = sh.make_shardings(model.param_specs(abstract))
            params_template = jax.tree_util.tree_map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                abstract, shardings)
        except Exception:
            params_template = None      # fall back to host-side restore
        params, opt_state, meta = checkpointing.load_checkpoint(
            args.load, finetune=args.finetune,
            iteration=getattr(args, "load_iters", None),
            params_template=params_template,
        )
        if params is not None:
            start_iteration = meta["iteration"]
            print(f" loaded checkpoint at iteration {start_iteration}")
            if not args.finetune:
                # elastic resume: continue the cumulative sample count and
                # the deterministic data order from where the checkpoint
                # left off (the resharding restore above already handled a
                # different dp x slice mesh); announce + JSONL-log a fleet
                # shape change against the saved run_shape.json
                consumed_samples = int(meta.get("consumed_samples", 0) or 0)
                get_counters()["samples"] = consumed_samples
                from megatron_llm_tpu import multislice
                multislice.announce_elastic_resume(
                    args.load, args, start_iteration, consumed_samples,
                    stream=getattr(telemetry, "stream", None))
        tracing.startup_completed("load_checkpoint", t_load,
                                  time.perf_counter())
    # the trainer's form of the tree (parallel/glu_pairs.py): under tp a
    # gated MLP's first projection is held with gate / up an axis of its
    # own; checkpoints (load above, save_checkpoint) keep the flat form
    lora_targets = tuple(
        t for t in args.lora_targets.split(",") if t) if args.lora_rank else ()
    # (a projection that will carry LoRA leaves stays flat)
    form = (None if glu_pairs.FIRST in lora_targets
            else glu_pairs.for_trainer)
    if params is None:
        with tracing.startup_span("init_params"):
            params = sh.init_params(model, jax.random.PRNGKey(args.seed),
                                    form=form)
    elif form is not None:
        params = form(params)
    if sh.axis_size("ffn") > 1:
        n_paired, n_flat = glu_pairs.count(params)
        print(f" gated first projections held [gate, up] paired over tp: "
              f"{n_paired} leaves ({n_flat} left flat: LoRA leaves or int8 "
              f"scales)", flush=True)
        if getattr(telemetry, "stream", None) is not None:
            telemetry.stream.emit({"kind": "glu_pairs", "paired": n_paired,
                                   "left_flat": n_flat})

    # interleaved VPP trains with the layer stack in stage-major order;
    # checkpoints stay in natural order (see pipeline.permute_layer_stack)
    vpp = pc.virtual_pipeline_model_parallel_size or 1
    from megatron_llm_tpu.parallel.pipeline import (
        convert_opt_state_layout,
        convert_params_layout,
    )
    with tracing.startup_span("shard_params"):
        params = convert_params_layout(
            params, args.num_layers, pc.pipeline_model_parallel_size, vpp,
            to_stage_major=True)
        opt_state = convert_opt_state_layout(
            opt_state, args.num_layers, pc.pipeline_model_parallel_size, vpp,
            to_stage_major=True)
        params = sh.shard_params(params, model.param_specs(params))
        if args.fp16 or args.bf16:
            dt = jnp.float16 if args.fp16 else jnp.bfloat16
            params = jax.tree_util.tree_map(lambda p: p.astype(dt), params)

    def save_natural(save_dir, it_, params_, opt_state_, scheduler_=None):
        if lora_base is not None:
            # export MERGED weights in the standard checkpoint format
            # (loadable anywhere a base checkpoint is); the lora-shaped
            # optimizer state is fresh-start-only, so drop it
            from megatron_llm_tpu.lora import merge_lora
            params_ = merge_lora(lora_base, params_)
            opt_state_ = None
        checkpointing.save_checkpoint(
            save_dir, it_,
            convert_params_layout(
                params_, args.num_layers, pc.pipeline_model_parallel_size,
                vpp, to_stage_major=False),
            convert_opt_state_layout(
                opt_state_, args.num_layers, pc.pipeline_model_parallel_size,
                vpp, to_stage_major=False),
            # closure fallback: `scheduler` is bound by call time, after
            # main builds it
            scheduler_ if scheduler_ is not None else scheduler,
            args=checkpointing.config_to_args(getattr(model, "cfg", None)),
            consumed_samples=get_counters().get("samples", 0),
            async_save=getattr(args, "async_save", False),
        )

    # LoRA: swap the trainable tree for low-rank adapters over the
    # frozen (already sharded + cast) base
    lora_base = None
    if args.lora_rank:
        if pc.pipeline_model_parallel_size > 1:
            raise SystemExit("--lora_rank supports pp=1 on the CLI path "
                             "(the lora.py library composes manually)")
        if args.load and not args.finetune:
            raise SystemExit(
                "--lora_rank with --load requires --finetune: LoRA runs "
                "start fresh from base weights (checkpoints are exported "
                "merged; there is no LoRA-shaped optimizer state to "
                "resume)")
        from megatron_llm_tpu.lora import LoraAdapter
        lora_base = params
        model = LoraAdapter(model, lora_base)
        lora = model.init_lora(
            args.lora_rank, jax.random.PRNGKey(args.seed + 1),
            alpha=args.lora_alpha, targets=lora_targets)
        params = sh.shard_params(lora, model.param_specs(lora))
        n_ad = model.num_params(params)
        print(f" > LoRA rank {args.lora_rank}: {n_ad/1e6:.2f}M adapter "
              f"params trainable, base frozen", flush=True)

    with tracing.startup_span("build_data"):
        train_iter, eval_iter = build_data_iterator(
            args, mesh, num_micro, consumed_samples=consumed_samples)

    optimizer = MegatronOptimizer(
        tc, params_dtype=jax.tree_util.tree_leaves(params)[0].dtype
    )
    scheduler = OptimizerParamScheduler(
        max_lr=tc.lr, min_lr=tc.min_lr,
        lr_warmup_steps=tc.lr_warmup_iters,
        lr_decay_steps=tc.lr_decay_iters or max(tc.train_iters, 1),
        lr_decay_style=tc.lr_decay_style,
        # `is not None`, not `or`: explicit 0.0 means ramp from zero
        start_wd=(tc.start_weight_decay
                  if tc.start_weight_decay is not None else tc.weight_decay),
        end_wd=(tc.end_weight_decay
                if tc.end_weight_decay is not None else tc.weight_decay),
        wd_incr_steps=max(tc.train_iters, 1),
        wd_incr_style=tc.weight_decay_incr_style,
    )
    scheduler.num_steps = start_iteration

    # phase-2 resume: optimizer + scheduler state (params came in phase 1;
    # the optimizer had to exist first to provide the restore template).
    # The template is abstract (jax.eval_shape) — materializing a real
    # optimizer state just to read shapes would transiently double the
    # optimizer-state footprint on exactly the large-model resumes that
    # need direct-to-device restore.
    if args.load and start_iteration and not args.finetune:
        opt_template = jax.eval_shape(
            lambda p: optimizer.init(convert_params_layout(
                glu_pairs.flat(p), args.num_layers,
                pc.pipeline_model_parallel_size, vpp, to_stage_major=False)),
            params)
        _, loaded_opt, _ = checkpointing.load_checkpoint(
            args.load, load_params=False,
            opt_state_template=opt_template, scheduler=scheduler,
        )
        if loaded_opt is not None:
            staged = convert_opt_state_layout(
                loaded_opt, args.num_layers,
                pc.pipeline_model_parallel_size, vpp, to_stage_major=True)
            if form is not None:
                staged = map_param_trees(form, staged)
            # re-place restored leaves where a fresh init would put them:
            # param-shaped moments/masters follow the params' shardings
            # (zeros_like preserves sharding); scalar step / grad-scaler
            # state replicates across the mesh
            from jax.sharding import NamedSharding, PartitionSpec

            def _replicated(t):
                return jax.device_put(t, NamedSharding(
                    mesh, PartitionSpec(*([None] * t.ndim))))

            psh = jax.tree_util.tree_map(lambda p: p.sharding, params)

            def _like_params(tree):
                if tree is None:
                    return None
                return jax.tree_util.tree_map(jax.device_put, tree, psh)

            opt_state = staged._replace(
                step=_replicated(staged.step),
                master_params=_like_params(staged.master_params),
                exp_avg=_like_params(staged.exp_avg),
                exp_avg_sq=_like_params(staged.exp_avg_sq),
                grad_scaler=jax.tree_util.tree_map(
                    _replicated, staged.grad_scaler),
            )
            print(" restored optimizer + scheduler state")

    handler = DistributedSignalHandler() if args.exit_signal_handler else None
    if handler:
        handler.install()

    # pp > 1 drives the pipelined engine through the same pretrain() loop
    # (custom train_step); eval needs a forward-only program, which the
    # pipelined step doesn't provide
    pipelined = pc.pipeline_model_parallel_size > 1
    custom_step = None
    if pipelined:
        from megatron_llm_tpu.parallel.pipeline import (
            build_pipeline_train_step,
        )
        with tracing.startup_span("build_train_step"):
            custom_step = build_pipeline_train_step(
                model, optimizer, pc, num_micro,
                layer_stats=args.log_layer_stats_interval > 0)
        if not opt_state:
            with tracing.startup_span("build_optimizer"):
                opt_state = optimizer.init(params)
    from megatron_llm_tpu.timers import Timers

    # metrics writer: wandb (or its JSONL offline fallback) and/or a
    # tensorboard-dir JSONL stream — one add_scalar code path either way
    writer = None
    if args.wandb_logger or args.tensorboard_dir:
        from megatron_llm_tpu.wandb_logger import WandbTBShim

        fallback = (os.path.join(args.tensorboard_dir, "metrics.jsonl")
                    if args.tensorboard_dir else "wandb_offline.jsonl")
        if args.tensorboard_dir:
            os.makedirs(args.tensorboard_dir, exist_ok=True)
        writer = WandbTBShim(
            config=checkpointing.config_to_args(getattr(model, "cfg", None)),
            project=args.wandb_project, entity=args.wandb_entity,
            name=args.wandb_name, run_id=args.wandb_id,
            api_key=args.wandb_api_key, fallback_path=fallback,
            resume="must" if args.wandb_resume else "allow",
            force_offline=not args.wandb_logger)

    if args.eval_only:
        # reference --eval_only: no training, one evaluation pass
        if pipelined:
            raise SystemExit(
                "--eval_only is not supported with pipeline parallelism "
                "(no forward-only program for the pipelined engine)")
        if eval_iter is None:
            raise SystemExit("--eval_only requires validation data")
        from megatron_llm_tpu.training import build_train_step
        eval_step = build_train_step(model, optimizer, pc, num_micro,
                                     forward_only=True)
        losses = [float(eval_step(params, next(eval_iter), None))
                  for _ in range(args.eval_iters)]
        print(f" eval_only: validation loss "
              f"{sum(losses) / len(losses):.6E}")
        telemetry.close()
        return

    try:
        params, opt_state, it = pretrain(
            model, params, tc, pc, train_iter,
            optimizer=optimizer,
            scheduler=scheduler,
            train_step=custom_step,
            save_fn=save_natural,
            resilience=resilience,
            telemetry=telemetry,
            timers=Timers(log_level=args.timing_log_level,
                          log_option=args.timing_log_option),
            log_params_norm=args.log_params_norm,
            log_num_zeros_in_grad=args.log_num_zeros_in_grad,
            log_layer_stats_interval=args.log_layer_stats_interval,
            writer=writer,
            tensorboard_log_interval=args.tensorboard_log_interval,
            log_timers=args.log_timers_to_tensorboard,
            log_memory=args.log_memory_to_tensorboard,
            log_batch_size=args.log_batch_size_to_tensorboard,
            log_world_size=args.log_world_size_to_tensorboard,
            log_validation_ppl=args.log_validation_ppl_to_tensorboard,
            log_interval=args.log_interval,
            save_interval=args.save_interval,
            async_save=getattr(args, "async_save", False),
            save_dir=args.save,
            eval_iterator=None if pipelined else eval_iter,
            eval_interval=(args.eval_interval
                           if eval_iter and not pipelined else None),
            eval_iters=args.eval_iters,
            exit_signal_handler=handler,
            start_iteration=start_iteration,
            opt_state=opt_state,
            skip_iters=getattr(args, "skip_iters", ()) or (),
            exit_interval=getattr(args, "exit_interval", None),
            exit_duration_in_mins=getattr(args, "exit_duration_in_mins",
                                          None),
            preempt_exit_code=getattr(args, "preempt_exit_code", 0) or 0,
        )
    finally:
        # stop the watchdog thread + uninstall the fault hook on every
        # exit path (signal-save exits via SystemExit mid-pretrain)
        if resilience is not None:
            resilience.close()
        # close after resilience: a crash path above may still want to
        # dump the flight recorder through the installed stream
        telemetry.close()

    if args.save:
        save_natural(args.save, it, params, opt_state)
        # flush a final --async_save before the interpreter starts tearing
        # down orbax's executor (a dangling dispatch races shutdown)
        checkpointing.finalize_async_saves()
        print(f" saved final checkpoint at iteration {it}")


if __name__ == "__main__":
    main()
