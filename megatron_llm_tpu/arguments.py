"""Argparse CLI surface.

Reference: ``megatron/arguments.py`` (1,103 LoC, 225 flags across 16
``_add_*_args`` groups, ~350 lines of ``validate_args`` cross-derivation).
The flag *names* are kept so reference launch scripts carry over with
``--device=tpu``; the grouping/derivations are
re-written for this framework.  Flags that are CUDA-implementation details
(``--masked_softmax_fusion``, ``--gradient_accumulation_fusion``, nvFuser
toggles, ``CUDA_DEVICE_MAX_CONNECTIONS`` checks, arguments.py:337-347) are
accepted-and-ignored for compatibility: XLA owns fusion and program order
on TPU.
"""

from __future__ import annotations

import argparse
import os
from typing import Callable, Optional

from megatron_llm_tpu.config import (
    ParallelConfig,
    TrainConfig,
    TransformerConfig,
    pattern_layer_types,
)


def build_base_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="megatron_llm_tpu arguments", allow_abbrev=False
    )
    _add_network_size_args(parser)
    _add_regularization_args(parser)
    _add_training_args(parser)
    _add_initialization_args(parser)
    _add_learning_rate_args(parser)
    _add_checkpointing_args(parser)
    _add_mixed_precision_args(parser)
    _add_distributed_args(parser)
    _add_validation_args(parser)
    _add_data_args(parser)
    _add_logging_args(parser)
    _add_telemetry_args(parser)
    _add_inference_args(parser)
    _add_resilience_args(parser)
    _add_compat_noop_args(parser)
    _add_unimplemented_compat_args(parser)
    return parser


def parse_args(
    extra_args_provider: Optional[Callable] = None,
    args_defaults: Optional[dict] = None,
    ignore_unknown_args: bool = False,
    args_list=None,
):
    """Reference: arguments.py:38 ``parse_args`` + entry-point extension
    hook (finetune.py:242-254)."""
    parser = build_base_parser()
    if extra_args_provider is not None:
        parser = extra_args_provider(parser)
    if ignore_unknown_args:
        args, _ = parser.parse_known_args(args_list)
    else:
        args = parser.parse_args(args_list)
    if args_defaults:
        for k, v in args_defaults.items():
            if getattr(args, k, None) is None:
                setattr(args, k, v)
    return args


# ---------------------------------------------------------------------------
# groups
# ---------------------------------------------------------------------------

def _add_network_size_args(parser):
    g = parser.add_argument_group("network size")
    g.add_argument("--num_layers", type=int, default=None)
    # encoder/decoder split names (reference: arguments.py encoder_num_layers
    # et al.; num_layers/seq_length fall back to the encoder_* values)
    g.add_argument("--encoder_num_layers", type=int, default=None)
    g.add_argument("--encoder_seq_length", type=int, default=None)
    g.add_argument("--hidden_size", type=int, default=None)
    g.add_argument("--ffn_hidden_size", type=int, default=None)
    g.add_argument("--num_attention_heads", type=int, default=None)
    g.add_argument("--num_attention_heads_kv", type=int, default=None)
    g.add_argument("--kv_channels", type=int, default=None)
    # mixture-of-experts (TPU-native extension; reference has no MoE)
    g.add_argument("--num_experts", type=int, default=0)
    g.add_argument("--moe_top_k", type=int, default=2)
    g.add_argument("--moe_ffn_hidden_size", type=int, default=None,
                   help="an expert's width where it is not "
                        "--ffn_hidden_size (moe_intermediate_size)")
    g.add_argument("--moe_score_function", type=str, default="softmax",
                   choices=["softmax", "sigmoid"],
                   help="how the router scores the experts")
    g.add_argument("--moe_choice_bias", type=int, default=0, choices=[0, 1],
                   help="a bias an expert added to the scores for the "
                        "choice only (e_score_correction_bias)")
    g.add_argument("--moe_choice_bias_std", type=float, default=None,
                   help="the spread a fresh model's choice bias is drawn "
                        "at (default 0.1; a checkpoint overwrites it)")
    g.add_argument("--moe_gate_norm_eps", type=float, default=None,
                   help="what guards --norm_topk_prob's division "
                        "(default: 1e-20 under a sigmoid router, 1e-9 "
                        "under a softmax)")
    g.add_argument("--moe_gate_norm_added", type=int, default=0,
                   choices=[0, 1],
                   help="1: the chosen scores' sum PLUS the epsilon "
                        "(lfm2); 0: the larger of the two")
    g.add_argument("--moe_routed_scale", type=float, default=1.0,
                   help="the chosen gates times this "
                        "(routed_scaling_factor)")
    g.add_argument("--moe_shared_experts", type=int, default=0,
                   help="an ungated MLP of this many experts' width that "
                        "every token passes through (n_shared_experts)")
    g.add_argument("--moe_shared_expert_gate", action="store_true",
                   help="the shared MLP's output times sigmoid(x w_s), "
                        "one gate a token (shared_expert_gate)")
    g.add_argument("--moe_first_dense_layers", type=int, default=0,
                   help="leading layers that keep a dense MLP of "
                        "--ffn_hidden_size (first_k_dense_replace)")
    g.add_argument("--moe_router_experts", type=int, default=None,
                   help="the experts the router scores where this chip "
                        "holds a share of them: --num_experts contiguous "
                        "ones from --moe_experts_first on (inference)")
    g.add_argument("--moe_experts_first", type=int, default=0)
    g.add_argument("--moe_n_group", type=int, default=1)
    g.add_argument("--moe_topk_group", type=int, default=1)
    g.add_argument("--kv_lora_rank", type=int, default=None,
                   help="latent attention: the width of the latent that "
                        "keys and values are expanded from, and cached")
    g.add_argument("--q_lora_rank", type=int, default=None,
                   help="latent attention's compressed query: the width "
                        "between the query's two projections (a norm "
                        "between them)")
    g.add_argument("--qk_nope_head_dim", type=int, default=128)
    g.add_argument("--qk_rope_head_dim", type=int, default=64)
    g.add_argument("--v_head_dim", type=int, default=128)
    g.add_argument("--moe_capacity_factor", type=float, default=1.25)
    g.add_argument("--moe_min_capacity", type=int, default=4)
    g.add_argument("--moe_aux_loss_coeff", type=float, default=1e-2)
    g.add_argument("--moe_z_loss_coeff", type=float, default=0.0)
    g.add_argument("--seq_length", type=int, default=None)
    # T5 decoder sequence length (reference: --decoder_seq_length,
    # megatron/arguments.py encoder/decoder seq args)
    g.add_argument("--decoder_seq_length", type=int, default=None)
    g.add_argument("--max_position_embeddings", type=int, default=None)
    g.add_argument("--make_vocab_size_divisible_by", type=int, default=128)
    g.add_argument("--padded_vocab_size", type=int, default=None)
    g.add_argument("--position_embedding_type", type=str, default="learned_absolute",
                   choices=["learned_absolute", "rotary", "none"])
    g.add_argument("--rope_scaling_factor", type=float, default=1.0)
    g.add_argument("--rope_theta", type=float, default=10000.0)
    g.add_argument("--rope_llama3_scaling", type=float, nargs=4,
                   default=None,
                   metavar=("FACTOR", "LOW_FREQ", "HIGH_FREQ", "ORIG_MAX"),
                   help="Llama-3.1 NTK-by-parts rope remap: factor "
                        "low_freq_factor high_freq_factor "
                        "original_max_position (e.g. 8 1 4 8192)")
    g.add_argument("--rope_yarn_scaling", type=float, nargs=5, default=None,
                   metavar=("FACTOR", "ORIG_MAX", "BETA_FAST", "BETA_SLOW",
                            "ATTENTION_FACTOR"),
                   help="YaRN rope remap; the last number multiplies cos "
                        "and sin (e.g. 16 8192 32 1 1.2772588722239782)")
    g.add_argument("--rope_yarn_layer_types", type=str, nargs="+",
                   default=None,
                   help="the --layer_types YaRN applies to (default: all)")
    g.add_argument("--rope_layer_types", type=str, nargs="+", default=None,
                   help="the --layer_types that rotate (default: all); a "
                        "layer of another type carries no positions")
    g.add_argument("--attention_output_gate", action="store_true",
                   help="the attention's output times sigmoid(gate(u)) "
                        "before its output projection, gate a fourth "
                        "projection of the layer's normed input (afmoe)")
    g.add_argument("--sublayer_output_norm", action="store_true",
                   help="four norms a layer: each sublayer's output is "
                        "normed as well as its input, x + norm(f(norm(x)))")
    g.add_argument("--loop_steps", type=int, default=1,
                   help="a looped stack (ouro's total_ut_steps): the layers "
                        "run this many times over the same weights, the "
                        "final norm after each pass, and a token holds "
                        "num_layers x loop_steps cache planes")
    g.add_argument("--layernorm_epsilon", type=float, default=1e-5)
    g.add_argument("--use_rms_norm", action="store_true")
    g.add_argument("--use_post_ln", action="store_true")
    g.add_argument("--glu_activation", type=str, default=None,
                   choices=[None, "liglu", "geglu", "reglu", "swiglu"])
    g.add_argument("--no_bias", action="store_false", dest="use_bias")
    g.add_argument("--use_bias", action="store_true", dest="use_bias")
    g.add_argument("--apply_residual_connection_post_layernorm",
                   action="store_true", dest="use_post_ln")
    g.add_argument("--init_method_xavier_uniform", action="store_true")
    g.add_argument("--parallel_attn", action="store_true")
    g.add_argument("--parallel_layernorm", action="store_true")
    g.add_argument("--sliding_window_size", type=int, default=None)
    g.add_argument("--layer_types", type=str, nargs="+", default=None,
                   help="one period of layer types, repeated over the "
                        "depth: sliding (--sliding_window_size keys) or "
                        "full (e.g. sliding sliding sliding full); or a "
                        "hybrid's mamba and attention (with moe: a "
                        "layer is one sublayer, an expert layer alone), "
                        "or conv and attention, every layer of the depth "
                        "spelled out where the pattern does not repeat; or "
                        "retention alone (power-retention layers); or "
                        "gated_delta and attention (delta-rule layers)")
    g.add_argument("--hybrid_override_pattern", type=str, default=None,
                   help="a letter a layer in place of --layer_types: M a "
                        "Mamba-2 mixer, * an attention mixer, E an "
                        "expert layer; the whole depth is one period")
    g.add_argument("--mamba_n_heads", type=int, default=128,
                   help="heads of a 'mamba' layer's state-space mixer")
    g.add_argument("--mamba_d_head", type=int, default=64)
    g.add_argument("--mamba_d_state", type=int, default=128)
    g.add_argument("--mamba_n_groups", type=int, default=1)
    g.add_argument("--mamba_d_conv", type=int, default=4)
    g.add_argument("--mamba_chunk_size", type=int, default=256,
                   help="the chunked scan's block (changes no result)")
    g.add_argument("--mamba_conv_bias", type=int, default=1, choices=[0, 1])
    g.add_argument("--conv_taps", type=int, default=3,
                   help="taps a channel of a 'conv' layer's gated short "
                        "convolution (conv_L_cache)")
    g.add_argument("--conv_mixer_bias", type=int, default=0, choices=[0, 1],
                   help="a bias a channel on that convolution (conv_bias)")
    g.add_argument("--delta_key_heads", type=int, default=16,
                   help="query/key heads of a 'gated_delta' layer's "
                        "delta-rule mixer (linear_num_key_heads)")
    g.add_argument("--delta_value_heads", type=int, default=32,
                   help="its value heads, a state each "
                        "(linear_num_value_heads)")
    g.add_argument("--delta_key_dim", type=int, default=128)
    g.add_argument("--delta_value_dim", type=int, default=128)
    g.add_argument("--delta_conv_taps", type=int, default=4,
                   help="taps a channel of its convolution over q, k and "
                        "v (linear_conv_kernel_dim)")
    g.add_argument("--attention_multiplier", type=float, default=None,
                   help="attention scores times this in place of "
                        "1/sqrt(head_dim)")
    g.add_argument("--residual_multiplier", type=float, default=1.0,
                   help="both residual branches times this")
    g.add_argument("--logits_scaling", type=float, default=1.0,
                   help="the logits divided by this")
    g.add_argument("--add_qkv_bias", action="store_true",
                   help="bias on the QKV projection only (Qwen2-style)")
    g.add_argument("--qk_norm", action="store_true",
                   help="RMSNorm on the whole query and key projections "
                        "before the rotary embedding (OLMoE)")
    g.add_argument("--qk_norm_per_head", action="store_true",
                   help="RMSNorm on each query and key head before the "
                        "rotary embedding, one scale of head_dim a layer "
                        "for each (Qwen3, Keye)")
    g.add_argument("--dsa_index_heads", type=int, default=0,
                   help="heads of the sparse-attention indexer; with any, "
                        "a query attends only its --dsa_topk best keys")
    g.add_argument("--dsa_index_head_dim", type=int, default=64)
    g.add_argument("--dsa_topk", type=int, default=2048,
                   help="keys a query attends under the indexer's choice")
    g.add_argument("--dsa_index_rope_dim", type=int, default=None,
                   help="the first so many dimensions of an indexer head "
                        "rotate and the rest pass (default: all of them)")
    g.add_argument("--dsa_index_query", default="input",
                   choices=["input", "compressed"],
                   help="what the indexer's query projection reads: the "
                        "layer's normed input, or the compressed query "
                        "(--q_lora_rank)")
    g.add_argument("--rope_sections", type=int, nargs="+", default=None,
                   help="rotary frequency pairs dealt to position streams "
                        "(mrope_section, e.g. 16 24 24)")
    g.add_argument("--norm_topk_prob", type=int, default=1, choices=[0, 1],
                   help="renormalise the chosen experts' gates to sum to "
                        "1 (Mixtral); 0 uses the softmax over all experts "
                        "as it is (OLMoE)")
    g.add_argument("--embedding_multiplier", type=float, default=None,
                   help="scale embedding output (Gemma: sqrt(hidden))")
    g.add_argument("--rotary_percent", type=float, default=1.0,
                   help="fraction of head dims that rotate "
                        "(GPT-NeoX/Pythia rotary_pct)")
    g.add_argument("--gelu_variant", default="tanh",
                   choices=["tanh", "exact"],
                   help="non-GLU MLP gelu: tanh-approximate (GPT-2) or "
                        "exact erf (Falcon/NeoX)")
    g.add_argument("--mlp_activation", default="gelu",
                   choices=["gelu", "relu2"],
                   help="the ungated MLP's nonlinearity: a gelu "
                        "(--gelu_variant) or relu(x)^2")
    g.add_argument("--no_tie_embed_logits", action="store_false",
                   dest="tie_embed_logits")


def _add_regularization_args(parser):
    g = parser.add_argument_group("regularization")
    g.add_argument("--attention_dropout", type=float, default=0.1)
    g.add_argument("--hidden_dropout", type=float, default=0.1)
    g.add_argument("--lima_dropout", action="store_true")
    g.add_argument("--weight_decay", type=float, default=0.01)
    g.add_argument("--start_weight_decay", type=float, default=None)
    g.add_argument("--end_weight_decay", type=float, default=None)
    g.add_argument("--weight_decay_incr_style", default="constant",
                   choices=["constant", "linear", "cosine"])
    g.add_argument("--clip_grad", type=float, default=1.0)
    g.add_argument("--adam_beta1", type=float, default=0.9)
    g.add_argument("--adam_beta2", type=float, default=0.999)
    g.add_argument("--adam_eps", type=float, default=1e-8)
    g.add_argument("--sgd_momentum", type=float, default=0.9)
    g.add_argument("--optimizer_state_dtype", default="fp32",
                   choices=["fp32", "bf16"],
                   help="storage dtype of Adam moments / SGD momentum "
                        "(bf16 halves optimizer-state memory+traffic; "
                        "step math stays fp32)")


def _add_training_args(parser):
    g = parser.add_argument_group("training")
    g.add_argument("--micro_batch_size", type=int, default=1)
    g.add_argument("--global_batch_size", type=int, default=None)
    g.add_argument("--rampup_batch_size", nargs=3, type=int, default=None)
    g.add_argument("--train_iters", type=int, default=None)
    g.add_argument("--exit_interval", type=int, default=None)
    g.add_argument("--exit_duration_in_mins", type=int, default=None)
    g.add_argument("--exit_signal_handler", action="store_true")
    g.add_argument("--optimizer", default="adam", choices=["adam", "sgd"])
    g.add_argument("--dataloader_type", default="single",
                   choices=["single", "cyclic"])
    g.add_argument("--recompute_granularity", default=None,
                   choices=[None, "full", "uniform", "block", "selective"])
    g.add_argument("--recompute_num_layers", type=int, default=1)
    # reference spellings: --recompute_activations == selective granularity,
    # --recompute_method picks the full-layer schedule (validate_args maps)
    g.add_argument("--recompute_activations", action="store_true")
    g.add_argument("--recompute_method", default=None,
                   choices=[None, "uniform", "block"])
    g.add_argument("--eval_only", action="store_true")
    g.add_argument("--skip_iters", type=int, nargs="*", default=[])
    g.add_argument("--use_flash_attn", action="store_true", default=True)
    g.add_argument("--no_flash_attn", action="store_false",
                   dest="use_flash_attn")
    # chunked head+CE: off by default at 32k vocab (a measured tie on
    # one v5e at commit `128e754`, not re-measured), auto-ON at >= 128k
    # vocab where the compile-level evidence is decisive (2.1x temp
    # memory, 1.3x HBM
    # traffic — docs/scale_aot.md); default=None distinguishes
    # "unspecified" from an explicit choice so validate_args can
    # auto-enable without overriding the user
    g.add_argument("--fused_lm_cross_entropy", action="store_const",
                   const=True, default=None)
    g.add_argument("--no_fused_lm_cross_entropy", action="store_const",
                   const=False, dest="fused_lm_cross_entropy")
    g.add_argument("--fused_ce_chunk_size", type=int, default=8192)


def _add_initialization_args(parser):
    g = parser.add_argument_group("initialization")
    g.add_argument("--seed", type=int, default=1234)
    g.add_argument("--data_parallel_random_init", action="store_true")
    g.add_argument("--init_method_std", type=float, default=0.02)


def _add_learning_rate_args(parser):
    g = parser.add_argument_group("learning rate")
    g.add_argument("--lr", type=float, default=None)
    g.add_argument("--lr_decay_style", default="linear",
                   choices=["constant", "linear", "cosine",
                            "inverse-square-root"])
    g.add_argument("--lr_decay_iters", type=int, default=None)
    g.add_argument("--lr_warmup_fraction", type=float, default=None)
    g.add_argument("--lr_warmup_iters", type=int, default=0)
    g.add_argument("--min_lr", type=float, default=0.0)


def _add_checkpointing_args(parser):
    g = parser.add_argument_group("checkpointing")
    g.add_argument("--save", type=str, default=None)
    g.add_argument("--save_interval", type=int, default=None)
    g.add_argument("--async_save", action="store_true",
                   help="background tensorstore writes; the tracker file "
                        "lands only once the data is durable")
    g.add_argument("--load", type=str, default=None)
    g.add_argument("--load_iters", type=int, default=None,
                   help="load this iteration instead of the tracker's latest")
    g.add_argument("--finetune", action="store_true")
    g.add_argument("--use_checkpoint_args", action="store_true")


def _add_mixed_precision_args(parser):
    g = parser.add_argument_group("mixed precision")
    g.add_argument("--fp16", action="store_true")
    g.add_argument("--bf16", action="store_true")
    g.add_argument("--loss_scale", type=float, default=None)
    g.add_argument("--initial_loss_scale", type=float, default=2.0 ** 32)
    g.add_argument("--min_loss_scale", type=float, default=1.0)
    g.add_argument("--loss_scale_window", type=int, default=1000)
    g.add_argument("--hysteresis", type=int, default=2)
    g.add_argument("--attention_softmax_in_fp32", action="store_true",
                   default=True)
    g.add_argument("--no_attention_softmax_in_fp32", action="store_false",
                   dest="attention_softmax_in_fp32")



def _add_distributed_args(parser):
    g = parser.add_argument_group("distributed")
    g.add_argument("--tensor_model_parallel_size", type=int, default=1)
    g.add_argument("--pipeline_model_parallel_size", type=int, default=1)
    g.add_argument("--num_layers_per_virtual_pipeline_stage", type=int,
                   default=None)
    g.add_argument("--sequence_parallel", action="store_true")
    g.add_argument("--context_parallel_size", type=int, default=1)
    g.add_argument("--context_parallel_algo", default="ring",
                   choices=["ring", "ulysses", "zigzag"],
                   help="cp attention algorithm: K/V ring (ppermute), "
                        "Ulysses all-to-all (heads %% cp == 0; falls back "
                        "to ring otherwise), or zigzag (load-balanced "
                        "causal ring: half-chunk pair layout + "
                        "fully-masked-block skipping; needs an even "
                        "seq/cp, falls back to ring otherwise)")
    g.add_argument("--use_distributed_optimizer", action="store_true")
    g.add_argument("--expert_model_parallel_size", type=int, default=1)
    # multi-slice (MegaScale-tier): DCN data parallelism across pod slices
    g.add_argument("--num_slices", type=int, default=1,
                   help="number of TPU pod slices joined over DCN; the mesh "
                        "gains an outer 'slice' axis and total data "
                        "parallelism is num_slices x data_parallel_size "
                        "(see docs/guide/multislice.md)")
    g.add_argument("--multislice_flat_reduce", action="store_true",
                   help="disable the explicit hierarchical (ICI-then-DCN) "
                        "gradient reduction and use one flat all-reduce "
                        "over ('slice','dp'), deferring DCN staging to the "
                        "compiler's collective lowering")
    g.add_argument("--preempt_exit_code", type=int, default=None,
                   help="process exit code after a consensus preemption "
                        "rescue save (default: 17 when --num_slices > 1 so "
                        "the fleet supervisor restarts the job, else 0 for "
                        "single-job backward compatibility)")
    g.add_argument("--device", default="tpu", choices=["tpu", "cpu"],
                   help="'cpu' pins JAX to the CPU; 'tpu' requires a chip "
                        "(no TPU is an error, not a CPU run) unless "
                        "JAX_PLATFORMS=cpu asks for the CPU "
                        "(initialize.select_platform)")


def _add_validation_args(parser):
    g = parser.add_argument_group("validation")
    g.add_argument("--eval_iters", type=int, default=100)
    g.add_argument("--eval_interval", type=int, default=1000)


def _add_data_args(parser):
    g = parser.add_argument_group("data")
    g.add_argument("--data_path", nargs="*", default=None)
    g.add_argument("--split", type=str, default="969,30,1")
    g.add_argument("--data_impl", default="mmap")
    g.add_argument("--num_workers", type=int, default=2)
    g.add_argument("--tokenizer_type", type=str, default=None)
    g.add_argument("--vocab_file", type=str, default=None)
    g.add_argument("--merge_file", type=str, default=None)
    g.add_argument("--tokenizer_path", type=str, default=None)
    # SentencePiece .model file (reference --tokenizer_model; takes
    # precedence over --vocab_file for SentencePieceTokenizer)
    g.add_argument("--tokenizer_model", type=str, default=None)
    g.add_argument("--vocab_extra_ids_list", type=str, default=None,
                   help="comma-separated literal tokens appended to the "
                        "vocab as additional special tokens")
    g.add_argument("--vocab_size", type=int, default=None)
    g.add_argument("--vocab_extra_ids", type=int, default=0)
    g.add_argument("--no_new_tokens", action="store_false", dest="new_tokens")
    g.add_argument("--variable_seq_lengths", action="store_true")
    g.add_argument("--scalar_loss_mask", type=float, default=0.0)
    g.add_argument("--data_type", default="gpt", choices=["gpt", "instruction"])


def _add_logging_args(parser):
    g = parser.add_argument_group("logging")
    g.add_argument("--log_interval", type=int, default=100)
    g.add_argument("--log_timers_to_tensorboard", action="store_true",
                   help="write per-phase timer scalars (train-step-time "
                        "et al.) to the metrics writer at log boundaries "
                        "(reference training.py:509-525 semantics; console "
                        "timer logging is always on)")
    g.add_argument("--timing_log_level", type=int, default=2,
                   choices=[0, 1, 2],
                   help="default 2 (reference: 0) — per-phase timers are "
                        "dispatch-side and effectively free under jit")
    g.add_argument("--timing_log_option", default="minmax",
                   choices=["max", "minmax", "all"])
    g.add_argument("--log_params_norm", action="store_true")
    g.add_argument("--log_num_zeros_in_grad", action="store_true")
    g.add_argument("--log_layer_stats_interval", type=int, default=0,
                   help="model-health observatory (health.py): every N "
                        "iterations emit per-layer grad/param/update L2 "
                        "norms + non-finite grad counts, computed on-"
                        "device inside the jitted step (fixed shape, zero "
                        "steady-state recompiles), into JSONL/TensorBoard/"
                        "flight recorder; a NaN/spike rewind then names "
                        "the offending layers. 0 (default) disables")
    g.add_argument("--log_batch_size_to_tensorboard", action="store_true")
    g.add_argument("--log_memory_to_tensorboard", action="store_true")
    g.add_argument("--log_world_size_to_tensorboard", action="store_true")
    g.add_argument("--log_validation_ppl_to_tensorboard",
                   action="store_true")
    g.add_argument("--tensorboard_log_interval", type=int, default=1)
    g.add_argument("--wandb_resume", action="store_true")
    g.add_argument("--tensorboard_dir", type=str, default=None)
    g.add_argument("--wandb_logger", action="store_true")
    g.add_argument("--wandb_project", type=str, default=None)
    g.add_argument("--wandb_entity", type=str, default=None)
    g.add_argument("--wandb_name", type=str, default=None)
    g.add_argument("--wandb_id", type=str, default=None)
    g.add_argument("--wandb_api_key", type=str, default=None)


def _add_telemetry_args(parser):
    """Unified runtime telemetry (telemetry.py; MegaScale arxiv
    2402.15627 §5 — per-step telemetry, in-situ profiler capture, flight
    recorder).  See docs/guide/observability.md."""
    g = parser.add_argument_group("telemetry")
    g.add_argument("--structured_log_dir", type=str, default=None,
                   help="write one JSONL record per log boundary "
                        "(telemetry.jsonl) with loss/lr/step time/"
                        "throughput/MFU/memory/recovery counters, and "
                        "keep a flight recorder of the last K step "
                        "records dumped here on watchdog fire/crash")
    g.add_argument("--flight_recorder_size", type=int, default=64,
                   help="how many step records the in-memory flight "
                        "recorder retains")
    g.add_argument("--status_port", type=int, default=None,
                   help="start a stdlib HTTP /health + /metrics endpoint "
                        "on process 0 serving the latest telemetry record "
                        "(step, loss, MFU, goodput_pct, recovery "
                        "counters) as JSON or Prometheus text — the "
                        "trainer-side twin of the serving /metrics")
    g.add_argument("--profile", action="store_true",
                   help="capture a jax.profiler trace of iterations "
                        "[profile_step_start, profile_step_end] during "
                        "training")
    g.add_argument("--profile_step_start", type=int, default=10,
                   help="first iteration inside the profiler trace "
                        "(leave warmup/compile outside the window)")
    g.add_argument("--profile_step_end", type=int, default=12,
                   help="last iteration inside the profiler trace")
    g.add_argument("--profile_dir", type=str, default=None,
                   help="trace output dir (default: "
                        "<structured_log_dir>/profile, else "
                        "./profile_trace)")
    g.add_argument("--profiler_port", type=int, default=None,
                   help="start jax.profiler.start_server on this port "
                        "for live TensorBoard capture")
    # span tracing + goodput + straggler/recompile diagnostics
    # (tracing.py; MegaScale §5's attribution layer)
    g.add_argument("--trace_dir", type=str, default=None,
                   help="enable span tracing: write a Chrome trace_event "
                        "trace.json here (load in ui.perfetto.dev), turn "
                        "on goodput accounting (goodput_pct in the JSONL "
                        "stream + finish summary) and recompile/straggler "
                        "detection; summarize with tools/trace_report.py")
    g.add_argument("--trace_buffer_size", type=int, default=100000,
                   help="span ring-buffer capacity; eviction drops the "
                        "oldest events (count reported as dropped_events)")
    g.add_argument("--straggler_threshold", type=float, default=1.5,
                   help="flag a host as a straggler when its per-section "
                        "time exceeds this multiple of the cross-host "
                        "median at a log boundary")


def _add_inference_args(parser):
    g = parser.add_argument_group("inference")
    # REST server limits (text_generation_server.py; previously the
    # hardcoded MAX_PROMPTS / MAX_TOKENS module constants)
    g.add_argument("--serve_max_prompts", type=int, default=128,
                   help="maximum prompts per /api request")
    g.add_argument("--serve_max_tokens", type=int, default=1024,
                   help="maximum tokens_to_generate per /api request")
    g.add_argument("--log_requests", action="store_true",
                   help="log each /api request payload (prompts are user "
                        "data — off by default)")
    # continuous-batching engine (serving/; docs/guide/serving.md)
    g.add_argument("--serve_engine", action="store_true",
                   help="serve through the continuous-batching engine "
                        "(slot-based paged KV cache, token-level "
                        "co-batching, SSE streaming) instead of one "
                        "locked generate() per request")
    g.add_argument("--serve_num_slots", type=int, default=8,
                   help="decode batch rows (max concurrently running "
                        "requests)")
    g.add_argument("--serve_block_size", type=int, default=16,
                   help="tokens per KV page")
    g.add_argument("--serve_num_blocks", type=int, default=0,
                   help="KV pool pages; 0 = full backing for every slot "
                        "at serve_max_model_len (no oversubscription)")
    g.add_argument("--serve_prefill_chunk", type=int, default=64,
                   help="prompt tokens per prefill call (bounds how long "
                        "a long prompt stalls running decodes)")
    g.add_argument("--serve_max_queue_depth", type=int, default=64,
                   help="admission-control queue bound; beyond it /api "
                        "returns 429 with Retry-After")
    g.add_argument("--serve_deadline_secs", type=float, default=120.0,
                   help="per-request deadline (queued or running); 0 "
                        "disables")
    g.add_argument("--serve_max_model_len", type=int, default=0,
                   help="max prompt+generated tokens per request; 0 = "
                        "model max_position_embeddings")
    g.add_argument("--serve_paged_kernel", choices=["auto", "on", "off"],
                   default="auto",
                   help="Pallas ragged paged-attention decode kernel "
                        "(ops/pallas/paged_attention.py): 'auto' uses it "
                        "for decode steps when the Pallas backend is "
                        "available (prefill chunks and CPU keep the XLA "
                        "gather branch), 'on' forces it, 'off' disables")
    g.add_argument("--serve_prefill_kernel", choices=["auto", "on", "off"],
                   default="auto",
                   help="Pallas ragged paged-attention prefill kernel "
                        "for [1, C] chunked-prefill calls "
                        "(ops/pallas/paged_attention.py): 'auto' uses it "
                        "when the Pallas backend is available, 'on' "
                        "forces it, 'off' keeps the dense XLA gather "
                        "branch")
    g.add_argument("--serve_speculative", type=int, default=0,
                   help="in-engine speculative decoding: host-side "
                        "prompt-lookup drafting (serving/drafter.py) "
                        "verified by a fixed-shape [slots, draft_k+1] "
                        "exact-greedy step on the paged cache; sampled-"
                        "temperature requests decode normally inside the "
                        "same program; 0 disables")
    g.add_argument("--serve_draft_k", type=int, default=4,
                   help="max draft tokens proposed per slot per "
                        "speculative verify step (the verify program's "
                        "compiled width is draft_k + 1)")
    g.add_argument("--serve_prefix_cache", type=int, default=1,
                   help="share KV pages across requests with equal "
                        "prompt prefixes (refcounted copy-on-write "
                        "pages, LRU reuse); 0 disables")
    g.add_argument("--serve_host_cache_bytes", type=int, default=0,
                   help="host-RAM budget (bytes) for the hierarchical "
                        "KV cache spill tier under the prefix cache: "
                        "pages falling off the HBM LRU spill "
                        "asynchronously and swap back in with one "
                        "fixed-shape host-to-device scatter on a later "
                        "prefix match (serving/host_cache.py); 0 "
                        "disables the tier")
    # serving resilience (serving/resilience.py;
    # docs/guide/fault_tolerance.md "Serving resilience")
    g.add_argument("--serve_watchdog_secs", type=float, default=0.0,
                   help="engine watchdog: when no dispatch completes "
                        "within this many seconds while work is pending, "
                        "dump diagnostics and restart the engine "
                        "in-process (requeueing interrupted requests); "
                        "0 disables")
    g.add_argument("--serve_preemption", type=int, default=1,
                   help="pool-pressure preemption: on an oversubscribed "
                        "--serve_num_blocks pool, evict a strictly-"
                        "larger running request back to the queue head "
                        "so a starving admission can proceed; 0 disables")
    g.add_argument("--serve_restart_backoff_secs", type=float, default=0.5,
                   help="base delay of the exponential restart-storm "
                        "backoff (repeated engine restarts within 60s)")
    g.add_argument("--serve_fault_inject", type=str, default="",
                   help="deterministic serving chaos spec, e.g. "
                        "'nan@12,hang@30:5,slow@40:250,oom@8' (1-based "
                        "engine dispatch indices; each trigger fires "
                        "once).  Testing only.")
    # SLO sentinel (serving/alerts.py; docs/guide/observability.md
    # "Alerting & incidents")
    g.add_argument("--serve_alerts", type=int, default=1,
                   help="SLO sentinel (serving/alerts.py): evaluate "
                        "burn-rate/threshold/rate alert rules over "
                        "/metrics on the alert-eval thread, surface "
                        "firing alerts in /metrics + schema-13 "
                        "alert_transition JSONL events, and capture a "
                        "postmortem bundle under "
                        "<structured_log_dir>/incidents on each firing; "
                        "0 disables the evaluator")
    g.add_argument("--alert_rules", type=str, default=None,
                   help="alert rule set replacing the built-in defaults: "
                        "inline JSON (a list of rule objects, or "
                        "{'interval_secs':..,'rules':[..]}) or a path "
                        "to a JSON file (see "
                        "serving/alerts.py DEFAULT_RULES for the rule "
                        "grammar)")
    g.add_argument("--alert_webhook", type=str, default=None,
                   help="POST every firing/resolved alert transition "
                        "to this URL as JSON (bounded retry with "
                        "backoff; delivery is best-effort and never "
                        "blocks serving)")


def _add_resilience_args(parser):
    """Fault-tolerance runtime (resilience.py; beyond-reference — the
    reference's only in-band recovery is the fp16 loss-scale skip).
    See docs/guide/fault_tolerance.md."""
    g = parser.add_argument_group("resilience")
    g.add_argument("--rewind_on_spike", action="store_true",
                   help="rewind to the last good host snapshot when the "
                        "loss goes non-finite or spikes past "
                        "spike_factor x its EMA")
    g.add_argument("--spike_factor", type=float, default=3.0,
                   help="loss > factor * EMA counts as a spike (0 "
                        "disables the spike test; non-finite always "
                        "counts)")
    g.add_argument("--spike_ema_beta", type=float, default=0.98,
                   help="EMA smoothing for the spike baseline")
    g.add_argument("--rewind_patience", type=int, default=1,
                   help="consecutive bad checks before rewinding")
    g.add_argument("--snapshot_interval", type=int, default=50,
                   help="iterations between in-host-memory state "
                        "snapshots (the rewind targets)")
    g.add_argument("--resilience_check_interval", type=int, default=0,
                   help="inspect loss/grad_norm every N iterations "
                        "(device sync each check); 0 = only at log "
                        "boundaries, which are synced anyway")
    g.add_argument("--rewind_lr_factor", type=float, default=1.0,
                   help="multiply the LR by this on every rewind "
                        "(e.g. 0.5 to back off after a blow-up)")
    g.add_argument("--max_rewinds", type=int, default=8,
                   help="abort after this many rewinds (a run that keeps "
                        "blowing up needs a human)")
    g.add_argument("--watchdog_timeout_secs", type=float, default=None,
                   help="arm the hang watchdog: if no iteration completes "
                        "within this budget, dump stacks + device memory, "
                        "rescue-save the latest snapshot, and exit 17")
    g.add_argument("--watchdog_no_hard_exit", action="store_true",
                   help="watchdog only diagnoses + rescue-saves; the "
                        "process is left running")
    g.add_argument("--save_total_limit", type=int, default=0,
                   help="keep only the newest N iter_* checkpoints "
                        "(0 = keep all)")
    g.add_argument("--save_retries", type=int, default=2,
                   help="retry a failed checkpoint save this many times "
                        "(exponential backoff)")
    g.add_argument("--save_retry_backoff", type=float, default=0.25,
                   help="initial save-retry backoff in seconds (doubles "
                        "per attempt)")
    g.add_argument("--fault_inject", type=str, default=None,
                   help="deterministic chaos spec for testing recovery, "
                        "e.g. 'nan@3,save_io*2,hang@5:2.0,sigterm@7' "
                        "(also via MEGATRON_FAULT_INJECT)")


def _add_compat_noop_args(parser):
    """Reference flags that are CUDA implementation details — accepted and
    ignored so A100 launch scripts run unchanged."""
    g = parser.add_argument_group("compat (ignored on TPU)")
    g.add_argument("--masked_softmax_fusion", action="store_true")
    g.add_argument("--no_masked_softmax_fusion", action="store_false",
                   dest="masked_softmax_fusion")
    g.add_argument("--bias_gelu_fusion", action="store_true")
    g.add_argument("--no_bias_gelu_fusion", action="store_false",
                   dest="bias_gelu_fusion")
    g.add_argument("--bias_dropout_fusion", action="store_true")
    g.add_argument("--no_bias_dropout_fusion", action="store_false",
                   dest="bias_dropout_fusion")
    g.add_argument("--gradient_accumulation_fusion", action="store_true")
    g.add_argument("--DDP_impl", default="local", choices=["local", "torch"])
    g.add_argument("--use_ring_exchange_p2p", action="store_true")
    g.add_argument("--empty_unused_memory_level", type=int, default=0)
    g.add_argument("--transformer_impl", default="local")
    g.add_argument("--fp8_e4m3", action="store_true")
    g.add_argument("--fp8_hybrid", action="store_true")
    g.add_argument("--fp8_margin", type=int, default=0)
    g.add_argument("--fp8_interval", type=int, default=1)
    g.add_argument("--fp8_amax_history_len", type=int, default=1)
    g.add_argument("--fp8_amax_compute_algo", default="most_recent")
    g.add_argument("--no_fp8_wgrad", action="store_false", dest="fp8_wgrad")
    g.add_argument("--barrier_with_L1_time", action="store_true",
                   default=True)
    g.add_argument("--no_async_tensor_model_parallel_allreduce",
                   action="store_true")
    g.add_argument("--no_contiguous_buffers_in_local_ddp",
                   action="store_false",
                   dest="use_contiguous_buffers_in_local_ddp")
    g.add_argument("--no_gradient_accumulation_fusion",
                   action="store_false", dest="gradient_accumulation_fusion")
    g.add_argument("--no_persist_layer_norm", action="store_true")
    g.add_argument("--no_scatter_gather_tensors_in_pipeline",
                   action="store_true")
    g.add_argument("--distribute_saved_activations", action="store_true")
    g.add_argument("--no_data_sharding", action="store_true")
    g.add_argument("--no_initialization", action="store_false",
                   dest="perform_initialization")
    g.add_argument("--use_cpu_initialization", action="store_true")
    g.add_argument("--standalone_embedding_stage", action="store_true")
    g.add_argument("--pipeline_model_parallel_split_rank", type=int,
                   default=None)
    g.add_argument("--adlr_autoresume", action="store_true")
    g.add_argument("--adlr_autoresume_interval", type=int, default=1000)
    # fp32_residual_connection / fp16_lm_cross_entropy: this framework
    # always keeps the residual stream in the compute dtype and computes
    # cross entropy in fp32 (better numerics; deliberate deviation)
    g.add_argument("--fp32_residual_connection", action="store_true")
    g.add_argument("--fp16_lm_cross_entropy", action="store_true")
    # query-key layer scaling is an fp16-overflow workaround (divide scores
    # by layer number, multiply back inside the fused softmax — net
    # mathematically neutral); softmax here always accumulates in fp32
    # unless --no_attention_softmax_in_fp32, so the trick has nothing to fix
    g.add_argument("--no_query_key_layer_scaling", action="store_true")
    g.add_argument("--onnx_safe", action="store_true")
    # grad-buffer dtype / DDP backend / torchrun rank plumbing: XLA owns
    # the reduction dtype and program order on TPU; jax.distributed owns
    # process bootstrap (nccl/gloo map to xla)
    g.add_argument("--accumulate_allreduce_grads_in_fp32",
                   action="store_true", default=True)
    g.add_argument("--distributed_backend", default="xla",
                   choices=["xla", "nccl", "gloo"])
    g.add_argument("--local_rank", type=int, default=None)
    # mmap page-prewarm and the tensorboardX writer queue are host-side
    # implementation details of the reference's loaders/writers
    g.add_argument("--mmap_warmup", action="store_true")
    g.add_argument("--tensorboard_queue_size", type=int, default=1000)


#: dest -> parser default for every flag in _add_unimplemented_compat_args;
#: validate_args warns loudly when one is set away from its default
_UNIMPLEMENTED_DEFAULTS = {
    "decoder_num_layers": None,
    "train_samples": None,
    "lr_decay_samples": None,
    "lr_warmup_samples": 0,
    "override_opt_param_scheduler": False,
    "use_checkpoint_opt_param_scheduler": False,
    "no_save_optim": False,
    "no_save_rng": False,
    "no_load_optim": False,
    "no_load_rng": False,
    "metrics": [],
    "train_data_path": None,
    "valid_data_path": None,
    "test_data_path": None,
    "reset_position_ids": False,
    "reset_attention_mask": False,
    "eod_mask_loss": False,
    "inference_batch_times_seqlen_threshold": 512,
    "max_tokens_to_oom": 12000,
}


def _add_unimplemented_compat_args(parser):
    """Reference features this stack does not implement (yet): the flags
    are accepted so A100 launch scripts parse unchanged, but setting one
    away from its default draws a loud validate_args warning instead of
    being silently ignored.  Implementing one means moving its
    ``add_argument`` back into a real group, deleting its
    ``_UNIMPLEMENTED_DEFAULTS`` entry, and reading ``args.<dest>``
    somewhere (the graft-lint ``flags`` checker enforces the read)."""
    g = parser.add_argument_group("unimplemented (accepted with a warning)")
    # T5 asymmetric-depth decoder
    g.add_argument("--decoder_num_layers", type=int, default=None)
    # sample-based (vs iteration-based) run length + lr schedule
    g.add_argument("--train_samples", type=int, default=None)
    g.add_argument("--lr_decay_samples", type=int, default=None)
    g.add_argument("--lr_warmup_samples", type=int, default=0)
    # scheduler-state checkpoint override policy
    g.add_argument("--override_opt_param_scheduler", action="store_true")
    g.add_argument("--use_checkpoint_opt_param_scheduler",
                   action="store_true")
    # partial checkpoint save/load (optimizer/rng exclusion)
    g.add_argument("--no_save_optim", action="store_true")
    g.add_argument("--no_save_rng", action="store_true")
    g.add_argument("--no_load_optim", action="store_true")
    g.add_argument("--no_load_rng", action="store_true")
    # extra validation metrics beyond loss/ppl
    g.add_argument("--metrics", nargs="*", default=[])
    # per-split dataset paths (use --data_path + --split)
    g.add_argument("--train_data_path", nargs="*", default=None)
    g.add_argument("--valid_data_path", nargs="*", default=None)
    g.add_argument("--test_data_path", nargs="*", default=None)
    # document-boundary resets inside packed sequences
    g.add_argument("--reset_position_ids", action="store_true")
    g.add_argument("--reset_attention_mask", action="store_true")
    g.add_argument("--eod_mask_loss", action="store_true")
    # reference text-generation heuristics (the serving engine's
    # admission control replaces them: --serve_max_tokens et al.)
    g.add_argument("--inference_batch_times_seqlen_threshold", type=int,
                   default=512)
    g.add_argument("--max_tokens_to_oom", type=int, default=12000)


# ---------------------------------------------------------------------------
# validation / derivation
# ---------------------------------------------------------------------------

def apply_fused_ce_policy(args, vocab=None):
    """Decide ``fused_lm_cross_entropy`` from the best-known vocab size.

    Policy (VERDICT r4 #7): off below 64k (a measured on-chip tie at
    32k, commit `128e754`, not re-measured), advisory note at 64k-128k,
    AUTO-ON at
    >= 128k where the compile-level evidence is decisive (temp memory
    3.20->1.51 GB, HBM traffic 25.5->20.1 GB, docs/scale_aot.md) — but
    only with an unsharded vocab: under tp>1 the fused path is inert
    (models/gpt.py gates on _vocab_unsharded) and we say so instead of
    advertising a saving that never engages.

    Idempotent and re-entrant: the user's explicit choice (tri-state
    flag, resolved on the FIRST call) always wins; non-explicit users
    get the policy recomputed as larger vocab estimates become known
    (tokenizer padding runs after validate_args; --use_checkpoint_args
    triggers a second validate_args pass)."""
    if vocab is None:
        vocab = max(getattr(args, "padded_vocab_size", 0) or 0,
                    getattr(args, "vocab_size", 0) or 0)
    if getattr(args, "fused_ce_user_explicit", None) is None:
        args.fused_ce_user_explicit = \
            getattr(args, "fused_lm_cross_entropy", None) is not None
    if args.fused_ce_user_explicit:
        return
    rank0 = getattr(args, "rank", 0) == 0
    tp = getattr(args, "tensor_model_parallel_size", 1) or 1
    if vocab >= 131072 and tp == 1:
        if not getattr(args, "fused_lm_cross_entropy", False) and rank0:
            print(" > vocab >= 128k: auto-enabling fused_lm_cross_entropy "
                  "(streams the head matmul + CE over vocab chunks; "
                  "opt out with --no_fused_lm_cross_entropy)", flush=True)
        args.fused_lm_cross_entropy = True
    else:
        args.fused_lm_cross_entropy = False
        if rank0 and vocab >= 131072:
            print(" > NOTE: vocab >= 128k but tensor-parallel vocab "
                  "sharding is active — fused_lm_cross_entropy is inert "
                  "under a sharded vocab (the vocab-parallel CE already "
                  "avoids the full logits); leaving it off", flush=True)
        elif rank0 and vocab >= 65536:
            print(" > NOTE: padded_vocab_size >= 64k — consider "
                  "--fused_lm_cross_entropy (see docs/scale_aot.md)",
                  flush=True)


def validate_args(args, world_size: Optional[int] = None):
    """Cross-derivations (reference: arguments.py:53-345)."""
    import jax

    # loud accept-and-ignore: unimplemented reference features parse fine
    # (launch scripts carry over) but never silently no-op when set
    if getattr(args, "rank", 0) == 0:
        for dest in sorted(_UNIMPLEMENTED_DEFAULTS):
            default = _UNIMPLEMENTED_DEFAULTS[dest]
            if getattr(args, dest, default) != default:
                print(f" > WARNING: --{dest} is accepted for launch-script "
                      f"compatibility but NOT implemented on this stack — "
                      f"ignoring it", flush=True)

    if world_size is None:
        world_size = int(os.environ.get("MEGATRON_TPU_WORLD_SIZE", 0)) or \
            len(jax.devices())

    mp = (args.tensor_model_parallel_size * args.pipeline_model_parallel_size
          * args.context_parallel_size)
    assert world_size % mp == 0, (
        f"world size ({world_size}) not divisible by tp "
        f"({args.tensor_model_parallel_size}) x pp "
        f"({args.pipeline_model_parallel_size}) x cp "
        f"({args.context_parallel_size})"
    )
    num_slices = int(getattr(args, "num_slices", 1) or 1)
    args.num_slices = num_slices
    assert world_size % num_slices == 0 and world_size % (mp * num_slices) == 0, (
        f"world size ({world_size}) not divisible by num_slices "
        f"({num_slices}) x tp x pp x cp ({mp})"
    )
    args.world_size = world_size
    # PER-SLICE dp (the mesh's dp axis); total data parallelism is
    # num_slices * data_parallel_size.  reference: arguments.py:76
    args.data_parallel_size = world_size // (mp * num_slices)
    # preemption policy: exit 17 (shared with the hang watchdog) so a
    # fleet supervisor restarts the job; single-job runs keep exit 0
    if getattr(args, "preempt_exit_code", None) is None:
        args.preempt_exit_code = 17 if num_slices > 1 else 0

    if getattr(args, "profile", False):
        assert args.profile_step_end >= args.profile_step_start, (
            f"--profile_step_end ({args.profile_step_end}) must be >= "
            f"--profile_step_start ({args.profile_step_start})")

    # virtual pipeline (reference: arguments.py:121-132)
    if args.num_layers_per_virtual_pipeline_stage is not None:
        assert args.pipeline_model_parallel_size > 1
        assert args.num_layers % args.pipeline_model_parallel_size == 0
        layers_per_pipeline = (
            args.num_layers // args.pipeline_model_parallel_size
        )
        assert layers_per_pipeline % args.num_layers_per_virtual_pipeline_stage == 0
        args.virtual_pipeline_model_parallel_size = (
            layers_per_pipeline // args.num_layers_per_virtual_pipeline_stage
        )
    else:
        args.virtual_pipeline_model_parallel_size = None

    # encoder/decoder spellings fall back onto the canonical names
    # (reference: arguments.py encoder_num_layers/encoder_seq_length)
    if args.num_layers is None and args.encoder_num_layers is not None:
        args.num_layers = args.encoder_num_layers
    if args.encoder_num_layers is None:
        args.encoder_num_layers = args.num_layers
    if args.seq_length is None and args.encoder_seq_length is not None:
        args.seq_length = args.encoder_seq_length
    if args.encoder_seq_length is None:
        args.encoder_seq_length = args.seq_length

    # recompute spellings (reference: --recompute_activations is the
    # selective policy; --recompute_method schedules full-layer recompute)
    if args.recompute_activations and args.recompute_granularity is None:
        args.recompute_granularity = "selective"
    if args.recompute_method and args.recompute_granularity in (None, "full"):
        args.recompute_granularity = args.recompute_method

    # dtype policy (reference: arguments.py:134-148)
    assert not (args.fp16 and args.bf16)
    args.params_dtype = "fp16" if args.fp16 else "bf16" if args.bf16 else "fp32"

    # batch math runs on TOTAL data parallelism (dp x slices)
    total_dp = args.data_parallel_size * args.num_slices
    if args.global_batch_size is None:
        args.global_batch_size = args.micro_batch_size * total_dp
    assert args.global_batch_size % (
        args.micro_batch_size * total_dp
    ) == 0, (
        f"global batch ({args.global_batch_size}) not divisible by micro "
        f"batch ({args.micro_batch_size}) x dp ({args.data_parallel_size}) "
        f"x slices ({args.num_slices})"
    )

    # big-vocab fused CE policy (VERDICT r4 #7) — one idempotent
    # helper, re-fired whenever the known vocab grows (tokenizer
    # padding, initialize_megatron's no-tokenizer padding, and a second
    # validate_args pass after --use_checkpoint_args)
    apply_fused_ce_policy(args)

    if args.ffn_hidden_size is None and args.hidden_size is not None:
        args.ffn_hidden_size = 4 * args.hidden_size
    if args.kv_channels is None and args.hidden_size is not None:
        args.kv_channels = args.hidden_size // args.num_attention_heads
    if args.max_position_embeddings is None:
        args.max_position_embeddings = args.seq_length
    if args.num_attention_heads_kv is None:
        args.num_attention_heads_kv = args.num_attention_heads

    # lr schedule derivations
    if args.lr_decay_iters is None and args.train_iters:
        args.lr_decay_iters = args.train_iters
    if args.lr_warmup_fraction is not None:
        args.lr_warmup_iters = int(
            args.lr_warmup_fraction * (args.lr_decay_iters or 0)
        )

    # SP requires TP > 1 (reference: arguments.py:329-335)
    if args.sequence_parallel and args.tensor_model_parallel_size == 1:
        args.sequence_parallel = False

    # The capacity factor shapes only the TRAINING path (inference is
    # dropless whatever it says, models/moe.py).  At c >= s*k/E, i.e.
    # factor >= E/top_k, training drops nothing either, but the
    # dispatch/combine one-hots are O(b*s*k*E*c) fp32 — at factor E/k that
    # is O(b*s^2*k) per microbatch and an easy OOM at long seq.  Warn here
    # (validate_args runs after --use_checkpoint_args adoption) rather
    # than silently training into it.
    if getattr(args, "num_experts", 0) and args.num_experts > 1:
        dropless = args.num_experts / max(args.moe_top_k, 1)
        if args.moe_capacity_factor >= dropless:
            print(
                f" > WARNING: moe_capacity_factor "
                f"({args.moe_capacity_factor:g}) >= num_experts/top_k "
                f"({dropless:g}) is a DROPLESS setting of the training "
                f"path; its dispatch buffers scale O(seq^2) with it at "
                f"seq_length={args.seq_length}.  For training, "
                f"--moe_capacity_factor 1.25 (the default) is the usual "
                f"choice.", flush=True,
            )

    return args


# ---------------------------------------------------------------------------
# lowering into config dataclasses
# ---------------------------------------------------------------------------

def transformer_config_from_args(args, model_name: Optional[str] = None
                                 ) -> TransformerConfig:
    return TransformerConfig(
        num_layers=args.num_layers,
        hidden_size=args.hidden_size,
        num_attention_heads=args.num_attention_heads,
        num_attention_heads_kv=args.num_attention_heads_kv,
        ffn_hidden_size=args.ffn_hidden_size,
        kv_channels=args.kv_channels,
        seq_length=args.seq_length,
        max_position_embeddings=args.max_position_embeddings,
        padded_vocab_size=args.padded_vocab_size,
        position_embedding_type=args.position_embedding_type,
        rope_scaling_factor=args.rope_scaling_factor,
        rope_theta=args.rope_theta,
        rope_llama3_scaling=(tuple(args.rope_llama3_scaling)
                             if getattr(args, "rope_llama3_scaling", None)
                             else None),
        rope_yarn_scaling=(tuple(args.rope_yarn_scaling)
                           if getattr(args, "rope_yarn_scaling", None)
                           else None),
        rope_yarn_layer_types=(tuple(args.rope_yarn_layer_types)
                               if getattr(args, "rope_yarn_layer_types", None)
                               else None),
        rope_layer_types=(tuple(args.rope_layer_types)
                          if getattr(args, "rope_layer_types", None)
                          else None),
        attention_output_gate=bool(
            getattr(args, "attention_output_gate", False)),
        sublayer_output_norm=bool(
            getattr(args, "sublayer_output_norm", False)),
        loop_steps=int(getattr(args, "loop_steps", 1) or 1),
        layer_types=(
            tuple(args.layer_types) if getattr(args, "layer_types", None)
            else pattern_layer_types(args.hybrid_override_pattern)
            if getattr(args, "hybrid_override_pattern", None) else None),
        tie_embed_logits=args.tie_embed_logits,
        normalization="rmsnorm" if args.use_rms_norm else "layernorm",
        layernorm_epsilon=args.layernorm_epsilon,
        use_post_ln=args.use_post_ln,
        glu_activation=args.glu_activation,
        add_bias_linear=args.use_bias,
        parallel_attn=args.parallel_attn,
        parallel_layernorm=args.parallel_layernorm,
        sliding_window_size=args.sliding_window_size,
        hidden_dropout=args.hidden_dropout,
        attention_dropout=args.attention_dropout,
        init_method_std=args.init_method_std,
        init_method_xavier_uniform=args.init_method_xavier_uniform,
        attention_softmax_in_fp32=args.attention_softmax_in_fp32,
        params_dtype=args.params_dtype,
        compute_dtype="bf16" if args.bf16 else "fp16" if args.fp16 else "fp32",
        recompute_granularity=args.recompute_granularity,
        recompute_num_layers=args.recompute_num_layers,
        lima_dropout=args.lima_dropout,
        use_flash_attn=args.use_flash_attn,
        fused_lm_cross_entropy=args.fused_lm_cross_entropy,
        fused_ce_chunk_size=args.fused_ce_chunk_size,
        num_experts=args.num_experts,
        moe_top_k=args.moe_top_k,
        moe_ffn_hidden_size=getattr(args, "moe_ffn_hidden_size", None),
        moe_capacity_factor=args.moe_capacity_factor,
        moe_min_capacity=args.moe_min_capacity,
        moe_aux_loss_coeff=args.moe_aux_loss_coeff,
        moe_z_loss_coeff=args.moe_z_loss_coeff,
        context_parallel_algo=args.context_parallel_algo,
        add_qkv_bias=getattr(args, "add_qkv_bias", False),
        embedding_multiplier=getattr(args, "embedding_multiplier", None),
        rotary_percent=getattr(args, "rotary_percent", 1.0),
        gelu_variant=getattr(args, "gelu_variant", "tanh"),
        mlp_activation=getattr(args, "mlp_activation", "gelu"),
        qk_norm=bool(getattr(args, "qk_norm", False)),
        norm_topk_prob=bool(getattr(args, "norm_topk_prob", True)),
        qk_norm_per_head=bool(getattr(args, "qk_norm_per_head", False)),
        dsa_index_heads=int(getattr(args, "dsa_index_heads", 0) or 0),
        dsa_index_head_dim=int(getattr(args, "dsa_index_head_dim", 64)),
        dsa_topk=int(getattr(args, "dsa_topk", 2048)),
        dsa_index_rope_dim=getattr(args, "dsa_index_rope_dim", None),
        dsa_index_query=getattr(args, "dsa_index_query", "input"),
        rope_sections=(tuple(args.rope_sections)
                       if getattr(args, "rope_sections", None) else None),
        moe_score_function=getattr(args, "moe_score_function", "softmax"),
        moe_choice_bias=bool(getattr(args, "moe_choice_bias", 0)),
        moe_choice_bias_std=getattr(args, "moe_choice_bias_std", None),
        moe_routed_scale=float(getattr(args, "moe_routed_scale", 1.0)),
        moe_shared_experts=int(getattr(args, "moe_shared_experts", 0) or 0),
        moe_first_dense_layers=int(
            getattr(args, "moe_first_dense_layers", 0) or 0),
        moe_n_group=int(getattr(args, "moe_n_group", 1)),
        moe_topk_group=int(getattr(args, "moe_topk_group", 1)),
        moe_router_experts=getattr(args, "moe_router_experts", None),
        moe_experts_first=int(getattr(args, "moe_experts_first", 0) or 0),
        mamba_n_heads=int(getattr(args, "mamba_n_heads", 128)),
        mamba_d_head=int(getattr(args, "mamba_d_head", 64)),
        mamba_d_state=int(getattr(args, "mamba_d_state", 128)),
        mamba_n_groups=int(getattr(args, "mamba_n_groups", 1)),
        mamba_d_conv=int(getattr(args, "mamba_d_conv", 4)),
        mamba_chunk_size=int(getattr(args, "mamba_chunk_size", 256)),
        mamba_conv_bias=bool(getattr(args, "mamba_conv_bias", 1)),
        conv_taps=int(getattr(args, "conv_taps", 3)),
        conv_mixer_bias=bool(getattr(args, "conv_mixer_bias", 0)),
        delta_key_heads=int(getattr(args, "delta_key_heads", 16)),
        delta_value_heads=int(getattr(args, "delta_value_heads", 32)),
        delta_key_dim=int(getattr(args, "delta_key_dim", 128)),
        delta_value_dim=int(getattr(args, "delta_value_dim", 128)),
        delta_conv_taps=int(getattr(args, "delta_conv_taps", 4)),
        moe_shared_expert_gate=bool(
            getattr(args, "moe_shared_expert_gate", False)),
        moe_gate_norm_eps=getattr(args, "moe_gate_norm_eps", None),
        moe_gate_norm_added=bool(getattr(args, "moe_gate_norm_added", 0)),
        attention_multiplier=getattr(args, "attention_multiplier", None),
        residual_multiplier=float(getattr(args, "residual_multiplier", 1.0)),
        logits_scaling=float(getattr(args, "logits_scaling", 1.0)),
        kv_lora_rank=getattr(args, "kv_lora_rank", None),
        q_lora_rank=getattr(args, "q_lora_rank", None),
        qk_nope_head_dim=int(getattr(args, "qk_nope_head_dim", 128)),
        qk_rope_head_dim=int(getattr(args, "qk_rope_head_dim", 64)),
        v_head_dim=int(getattr(args, "v_head_dim", 128)),
    )


def train_config_from_args(args) -> TrainConfig:
    return TrainConfig(
        micro_batch_size=args.micro_batch_size,
        global_batch_size=args.global_batch_size,
        rampup_batch_size=(tuple(args.rampup_batch_size)
                           if args.rampup_batch_size else None),
        train_iters=args.train_iters or 0,
        optimizer=args.optimizer,
        lr=args.lr or 1e-4,
        min_lr=args.min_lr,
        lr_decay_style=args.lr_decay_style,
        lr_decay_iters=args.lr_decay_iters,
        lr_warmup_iters=args.lr_warmup_iters,
        weight_decay=args.weight_decay,
        start_weight_decay=args.start_weight_decay,
        end_weight_decay=args.end_weight_decay,
        weight_decay_incr_style=args.weight_decay_incr_style,
        adam_beta1=args.adam_beta1,
        adam_beta2=args.adam_beta2,
        adam_eps=args.adam_eps,
        sgd_momentum=args.sgd_momentum,
        optimizer_state_dtype=args.optimizer_state_dtype,
        clip_grad=args.clip_grad,
        fp16=args.fp16,
        bf16=args.bf16,
        loss_scale=args.loss_scale,
        initial_loss_scale=args.initial_loss_scale,
        min_loss_scale=args.min_loss_scale,
        loss_scale_window=args.loss_scale_window,
        hysteresis=args.hysteresis,
        seed=args.seed,
        data_parallel_random_init=args.data_parallel_random_init,
    )


def parallel_config_from_args(args) -> ParallelConfig:
    return ParallelConfig(
        tensor_model_parallel_size=args.tensor_model_parallel_size,
        pipeline_model_parallel_size=args.pipeline_model_parallel_size,
        data_parallel_size=args.data_parallel_size,
        virtual_pipeline_model_parallel_size=args.virtual_pipeline_model_parallel_size,
        sequence_parallel=args.sequence_parallel,
        use_distributed_optimizer=args.use_distributed_optimizer,
        expert_model_parallel_size=args.expert_model_parallel_size,
        context_parallel_size=args.context_parallel_size,
        num_slices=getattr(args, "num_slices", 1) or 1,
        multislice_hierarchical=_resolve_hierarchical(args),
    )


def _resolve_hierarchical(args) -> bool:
    """Explicit ICI-then-DCN staging of the step's one gradient reduction
    is on for pure-DP multi-slice runs unless --multislice_flat_reduce opts
    out; with in-slice model parallelism (tp/pp/cp > 1), where the staged
    sum is not yet held to the flat one by a test, the reduction is one
    flat psum over ('slice','dp')."""
    if (getattr(args, "num_slices", 1) or 1) <= 1:
        return False
    if getattr(args, "multislice_flat_reduce", False):
        return False
    return (args.tensor_model_parallel_size == 1
            and args.pipeline_model_parallel_size == 1
            and args.context_parallel_size == 1)
