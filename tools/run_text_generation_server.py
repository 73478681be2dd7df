#!/usr/bin/env python
"""Load a checkpoint and serve generation over REST
(reference: tools/run_text_generation_server.py)."""

import time

_FIRST_STAMP = time.perf_counter()  # before the imports: tracing's timeline

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from megatron_llm_tpu import checkpointing, global_vars, telemetry, tracing
from megatron_llm_tpu.arguments import transformer_config_from_args
from megatron_llm_tpu.initialize import initialize_megatron
from megatron_llm_tpu.models import MODEL_REGISTRY
from megatron_llm_tpu.parallel import sharding as sh
from megatron_llm_tpu.serving import EngineConfig, InferenceEngine
from megatron_llm_tpu.text_generation_server import (
    MegatronServer, build_server_alerts)

tracing.startup_completed("imports", _FIRST_STAMP, time.perf_counter())


def extra_args(parser):
    g = parser.add_argument_group("server")
    g.add_argument("--model_name", required=True)
    g.add_argument("--port", type=int, default=5000)
    g.add_argument("--host", default="0.0.0.0")
    g.add_argument("--int8_weights", action="store_true",
                   help="weight-only int8 quantization of the linear "
                        "kernels at load (halves decode weight traffic; "
                        "docs/guide/inference.md)")
    g.add_argument("--int8_kv_cache", action="store_true",
                   help="store decode K/V as int8 with per-position "
                        "scales (halves KV HBM traffic — the dominant "
                        "bytes at long context)")
    return parser


def build_server(args, argv):
    """Model, weights, engine (warmed up and started) and the HTTP server
    for parsed ``args``; ``argv`` is the command line they came from
    (the model presets fill only flags it does not carry).  Returns the
    ``MegatronServer``, ready to ``run``."""
    # serving observability: --structured_log_dir streams request_done
    # JSONL (analyze offline with tools/serve_report.py), --trace_dir
    # records Chrome spans with per-request trace ids (merge with the
    # router's file via tools/trace_report.py --merge)
    if args.structured_log_dir:
        telemetry.install_stream(
            telemetry.TelemetryStream(args.structured_log_dir))
    trace_bundle = tracing.build_tracing(args)
    if trace_bundle is not None:
        tracing.start_trace_flusher(trace_bundle)
    # same per-model presets and derivations as finetune.py: the CLI is
    # self-sufficient (--model_name=llama2 implies rotary/swiglu/
    # rmsnorm/no-bias; gemma gets its sqrt(hidden) embedding scale)
    # (a family's lazy imports happen inside build_model and count there)
    with tracing.startup_span("build_model", model_name=args.model_name):
        from finetune import (MODEL_DEFAULTS, _apply_model_defaults,
                              model_provider)
        if args.model_name in MODEL_DEFAULTS:
            _apply_model_defaults(args, argv)
            model = model_provider(args)
        else:
            model = MODEL_REGISTRY[args.model_name](
                transformer_config_from_args(args)
            )
    if args.load:
        with tracing.startup_span("load_checkpoint"):
            params, _, _ = checkpointing.load_checkpoint(args.load,
                                                         finetune=True)
    else:
        print(" no --load given: serving a randomly initialized model")
        with tracing.startup_span("init_params"):
            params = sh.init_params(model, jax.random.PRNGKey(args.seed))
    with tracing.startup_span("shard_params"):
        specs = model.param_specs(params)
        if args.int8_weights:
            from megatron_llm_tpu.quantization import (
                quantize_linear_weights_int8, quantize_param_specs,
                quantized_weight_bytes)
            params = quantize_linear_weights_int8(params)
            specs = quantize_param_specs(specs, params)
            qb, fb = quantized_weight_bytes(params)
            print(f" int8 weights: {qb/1e6:.1f} MB int8 + "
                  f"{fb/1e6:.1f} MB float")
        params = sh.shard_params(params, specs)
    tokenizer = global_vars.get_tokenizer()
    engine = None
    if args.serve_engine:
        engine = InferenceEngine(model, params, EngineConfig(
            num_slots=args.serve_num_slots,
            block_size=args.serve_block_size,
            num_blocks=args.serve_num_blocks,
            max_model_len=args.serve_max_model_len,
            prefill_chunk=args.serve_prefill_chunk,
            max_queue_depth=args.serve_max_queue_depth,
            default_deadline_secs=args.serve_deadline_secs,
            int8_kv_cache=args.int8_kv_cache,
            prefix_cache=bool(args.serve_prefix_cache),
            host_cache_bytes=args.serve_host_cache_bytes,
            paged_kernel=args.serve_paged_kernel,
            prefill_kernel=args.serve_prefill_kernel,
            speculative=bool(args.serve_speculative),
            draft_k=args.serve_draft_k,
            watchdog_secs=args.serve_watchdog_secs,
            preemption=bool(args.serve_preemption),
            fault_spec=args.serve_fault_inject,
            restart_backoff_secs=args.serve_restart_backoff_secs,
        ))
        print(" * warming up serving engine (compiling prefill/decode "
              "programs)...", flush=True)
        print(f" * paged-attention decode path: {engine.paged_kernel}",
              flush=True)
        print(f" * paged-attention prefill path: {engine.prefill_kernel}",
              flush=True)
        spec = (f"on (draft_k={engine.draft_k})"
                if engine.speculative else "off")
        print(f" * speculative decoding: {spec}", flush=True)
        engine.warmup()
        tr = tracing.get_tracing()
        if tr is not None and tr.recompile is not None:
            tr.recompile.mark_steady()
        engine.start()
    server = MegatronServer(model, params, tokenizer,
                            int8_kv_cache=args.int8_kv_cache,
                            engine=engine,
                            log_requests=args.log_requests,
                            max_prompts=args.serve_max_prompts,
                            max_tokens=args.serve_max_tokens)
    # without an engine the server itself is what is ready (with one, the
    # timeline closed at engine.start() and this is nothing)
    tracing.startup_ready()
    # SLO sentinel (serving/alerts.py): burn-rate + threshold alerting
    # over this replica's own /metrics, postmortem bundles under
    # <structured_log_dir>/incidents, transitions on the JSONL stream
    if args.serve_alerts:
        build_server_alerts(server, engine=engine,
                            structured_log_dir=args.structured_log_dir,
                            alert_rules=args.alert_rules,
                            alert_webhook=args.alert_webhook)
    return server


def main():
    args = initialize_megatron(extra_args_provider=extra_args)
    build_server(args, sys.argv[1:]).run(args.host, args.port)


if __name__ == "__main__":
    main()
