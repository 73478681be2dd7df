"""Standalone tiny-model serving replica for router tests.

Spawned as a subprocess (one real engine process per replica, like a
production fleet):

    python tests/_serve_replica.py

Prints ``PORT <n>`` on stdout once the HTTP server is accepting, then
serves until killed.  Uses the same tiny llama + numeric fake tokenizer
as tests/test_serving_http.py, so prompts are space-separated ints and
greedy outputs are deterministic across replicas.

``--paged_kernel {auto,on,off}`` selects the paged-attention decode
path and ``--prefill_kernel {auto,on,off}`` the chunked-prefill path;
``on`` additionally flips the Pallas kernels into interpret mode so the
kernel-vs-XLA serve_bench A/Bs run end-to-end on CPU.
"""

import argparse
import os
import signal
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from megatron_llm_tpu.models.llama import LlamaModel, llama_config  # noqa: E402
from megatron_llm_tpu.serving import EngineConfig, InferenceEngine  # noqa: E402
from megatron_llm_tpu.text_generation_server import (  # noqa: E402
    MegatronServer, build_server_alerts)


class _FakeTokenizer:
    vocab_size = 64
    eod = 63
    pad = 0

    def tokenize(self, text):
        return [int(t) % 64 for t in text.split()]

    def detokenize(self, ids):
        return " ".join(str(i) for i in ids)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--paged_kernel", choices=["auto", "on", "off"],
                   default="auto")
    p.add_argument("--prefill_kernel", choices=["auto", "on", "off"],
                   default="auto")
    p.add_argument("--structured_log_dir", default=None,
                   help="stream request_done JSONL (trace-id e2e tests)")
    p.add_argument("--trace_dir", default=None,
                   help="write Chrome trace spans with trace ids")
    p.add_argument("--serve_fault_inject", default="",
                   help="chaos spec (e.g. 'nan@12,hang@20:5'); see "
                        "serving/resilience.py")
    p.add_argument("--serve_watchdog_secs", type=float, default=0.0,
                   help="engine watchdog timeout; 0 disables")
    p.add_argument("--serve_num_blocks", type=int, default=0,
                   help="KV pool pages; 0 = full per-slot backing")
    p.add_argument("--serve_host_cache_bytes", type=int, default=0,
                   help="host-RAM spill tier budget; 0 disables")
    p.add_argument("--serve_max_queue_depth", type=int, default=32,
                   help="admission queue bound (fleet-autoscale tests "
                        "raise it so a spike backlogs instead of 429s)")
    p.add_argument("--serve_deadline_secs", type=float, default=60.0,
                   help="default per-request deadline")
    p.add_argument("--serve_speculative", type=int, default=0,
                   help="1 = prompt-lookup speculative decoding "
                        "(fixed-shape K+1 verify step)")
    p.add_argument("--serve_draft_k", type=int, default=4,
                   help="max draft tokens per slot per verify step")
    p.add_argument("--serve_alerts", type=int, default=0,
                   help="1 = run the SLO sentinel (serving/alerts.py); "
                        "off by default so router tests stay quiet")
    p.add_argument("--alert_rules", default=None,
                   help="inline JSON or path overriding the built-in "
                        "alert rules (chaos tests use tight windows)")
    p.add_argument("--alert_webhook", default=None,
                   help="POST firing/resolved transitions to this URL")
    args = p.parse_args()
    if args.structured_log_dir:
        from megatron_llm_tpu import telemetry
        telemetry.install_stream(
            telemetry.TelemetryStream(args.structured_log_dir))
    if args.trace_dir:
        from megatron_llm_tpu import tracing
        bundle = tracing.Tracing(tracer=tracing.SpanTracer(),
                                 trace_dir=args.trace_dir)
        tracing.install_tracing(bundle)
        tracing.start_trace_flusher(bundle, interval_secs=0.5)
    if args.paged_kernel == "on" or args.prefill_kernel == "on":
        # no TPU in the test environment: run the Pallas kernels in
        # interpret mode so kernel_available() is true on CPU
        from megatron_llm_tpu.ops.pallas import paged_attention
        paged_attention._INTERPRET = True
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64,
        num_blocks=args.serve_num_blocks,
        host_cache_bytes=args.serve_host_cache_bytes,
        max_queue_depth=args.serve_max_queue_depth,
        default_deadline_secs=args.serve_deadline_secs,
        paged_kernel=args.paged_kernel,
        prefill_kernel=args.prefill_kernel,
        speculative=bool(args.serve_speculative),
        draft_k=args.serve_draft_k,
        watchdog_secs=args.serve_watchdog_secs,
        fault_spec=args.serve_fault_inject,
        restart_backoff_secs=0.0))
    engine.warmup()
    engine.start()
    server = MegatronServer(model, params, _FakeTokenizer(),
                            engine=engine, max_prompts=4, max_tokens=32)
    if args.serve_alerts:
        build_server_alerts(server, engine=engine,
                            structured_log_dir=args.structured_log_dir,
                            alert_rules=args.alert_rules,
                            alert_webhook=args.alert_webhook)
    # run() lives on a worker thread here, so the server can't install
    # its own SIGTERM hook — wire the graceful drain from the main thread
    signal.signal(signal.SIGTERM, lambda *_: server.begin_drain("SIGTERM"))
    t = threading.Thread(target=server.run,
                         kwargs={"host": "127.0.0.1", "port": 0},
                         daemon=True)
    t.start()
    for _ in range(200):
        if getattr(server, "httpd", None) is not None:
            break
        time.sleep(0.05)
    assert server.httpd is not None
    # single buffered write + flush → one atomic os.write: the server
    # thread prints its banner concurrently, and print()'s separate
    # text/newline writes can interleave with it mid-line
    sys.stdout.write(f"PORT {server.httpd.server_address[1]}\n")
    sys.stdout.flush()
    t.join()


if __name__ == "__main__":
    sys.exit(main())
