"""Framework initialization.

Reference: ``megatron/initialize.py`` — ``initialize_megatron`` (:26-66)
parses/validates args, sets globals, boots torch.distributed + process
groups (:124-193), seeds RNGs per (pp, dp) rank.

TPU: ``jax.distributed.initialize`` (multi-host only) + one Mesh; RNG
seeding is key-folding (``megatron_llm_tpu/random.py``), so "set the seed"
is just recording it in args.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

import jax

from megatron_llm_tpu import arguments, global_vars, topology, tracing
from megatron_llm_tpu.timers import Timers

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.

    ``JAX_COMPILATION_CACHE_DIR`` places it from outside (JAX reads the
    variable itself, so no directory is set here); otherwise it lives at
    the fixed ``<checkout>/.jax_cache`` — the path is part of the cache
    key, so a directory that moves between runs never hits."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def select_platform(device: str = "tpu") -> str:
    """Pin the CPU when it was asked for; otherwise insist on a TPU.

    The CPU is used only on request: ``--device=cpu``, or a
    ``JAX_PLATFORMS`` (``jax_platforms``) that names it first.  Without
    such a request a default backend other than ``tpu`` means JAX found
    no chip and fell back by itself — an error, never a run."""
    if device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    asked_cpu = (jax.config.jax_platforms or "").split(",")[0] == "cpu"
    backend = jax.default_backend()
    if backend != "tpu" and not asked_cpu:
        raise SystemExit(
            f"no TPU found: JAX's default backend is {backend!r}.  This "
            f"program does not fall back to the CPU by itself; to run on "
            f"the CPU ask for it with --device=cpu or JAX_PLATFORMS=cpu.")
    return backend


def initialize_megatron(
    extra_args_provider: Optional[Callable] = None,
    args_defaults: Optional[dict] = None,
    ignore_unknown_args: bool = False,
    args_list=None,
):
    """Parse + validate args, build the mesh, set globals.  Returns args."""
    with tracing.startup_span("initialize"):
        return _initialize(extra_args_provider, args_defaults,
                           ignore_unknown_args, args_list)


def _initialize(extra_args_provider, args_defaults, ignore_unknown_args,
                args_list):
    args = arguments.parse_args(
        extra_args_provider, args_defaults, ignore_unknown_args, args_list
    )

    # multi-host bootstrap over DCN (no-op single host); it must precede
    # the first backend query, which select_platform makes and which
    # brings the backend up
    with tracing.startup_span("runtime_init"):
        topology.initialize_distributed()
        backend = select_platform(args.device)
    if backend != "cpu":
        # an accelerator's programs take minutes to compile; XLA:CPU's
        # are cheap, and cached ones are tied to the host's CPU features
        enable_compile_cache()

    args = arguments.validate_args(args)

    # tokenizer before padded vocab is needed by the model
    tokenizer = None
    if args.tokenizer_type is not None:
        from megatron_llm_tpu.tokenizer import build_tokenizer

        tokenizer = build_tokenizer(args)   # sets args.padded_vocab_size
    elif args.padded_vocab_size is None and args.vocab_size is not None:
        mult = args.make_vocab_size_divisible_by * args.tensor_model_parallel_size
        v = args.vocab_size
        args.padded_vocab_size = ((v + mult - 1) // mult) * mult
        # padding can cross the fused-CE auto-on threshold (a vocab one
        # padding multiple below 128k) — re-fire the policy
        from megatron_llm_tpu.arguments import apply_fused_ce_policy
        apply_fused_ce_policy(args)

    timers = Timers(log_level=args.timing_log_level)
    global_vars.set_global_variables(args, tokenizer=tokenizer, timers=timers)

    from megatron_llm_tpu.microbatches import build_num_microbatches_calculator

    global_vars.set_num_microbatches_calculator(
        build_num_microbatches_calculator(
            args.global_batch_size, args.micro_batch_size,
            # total data parallelism: per-slice dp x slices
            args.data_parallel_size * args.num_slices,
            args.rampup_batch_size,
        )
    )

    topology.initialize_model_parallel(
        tensor_model_parallel_size=args.tensor_model_parallel_size,
        pipeline_model_parallel_size=args.pipeline_model_parallel_size,
        virtual_pipeline_model_parallel_size=args.virtual_pipeline_model_parallel_size,
        context_parallel_size=args.context_parallel_size,
        num_slices=args.num_slices,
    )
    return args
