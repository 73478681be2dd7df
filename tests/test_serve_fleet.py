"""Fleet supervisor chaos e2e (slow tier).

Real tiny-model engine subprocesses (tests/_serve_replica.py) under a
live :class:`FleetSupervisor`:

* a piecewise-rate spike (serve_bench ``--rate_schedule``) breaches the
  queue-depth SLO -> the supervisor spawns a replica -> post-scale-up
  TTFT p95 recovers, with zero dropped requests and zero engine
  restarts;
* a mid-burst SIGKILL is healed by respawn under the same slot while
  the router's failover finishes the burst exactly once;
* the supervisor control loop itself (observe/decide/act + brownout)
  adds ZERO steady-state compiles to an in-process engine it manages.
"""

import json
import os
import signal
import sys
import threading
import time
from pathlib import Path

import pytest

from megatron_llm_tpu.serving.router import ReplicaRouter, RouterServer
from megatron_llm_tpu.serving.supervisor import (
    FleetSupervisor,
    LocalProcessBackend,
    PolicyConfig,
    ReplicaBackend,
)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
import serve_bench  # noqa: E402

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _replica_backend(spawn_eta_secs=90.0):
    """LocalProcessBackend over the tiny-model replica, queue bound
    raised so a spike backlogs (visible queue depth) instead of 429ing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # single-device child, no 8-dev mesh
    return LocalProcessBackend(
        [sys.executable, os.path.join(ROOT, "tests", "_serve_replica.py"),
         "--serve_max_queue_depth", "2048",
         "--serve_deadline_secs", "600"],
        env=env, cwd=ROOT, spawn_eta_secs=spawn_eta_secs)


def _start_router_server(router):
    srv = RouterServer(router)
    threading.Thread(target=srv.run,
                     kwargs={"host": "127.0.0.1", "port": 0},
                     daemon=True).start()
    for _ in range(100):
        if srv.httpd is not None:
            break
        time.sleep(0.05)
    assert srv.httpd is not None
    return srv, f"http://127.0.0.1:{srv.httpd.server_address[1]}"


def _wait(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.25)
    raise AssertionError(f"timed out waiting for {what}")


def test_autoscale_spike_recovers_with_zero_drops(tmp_path):
    """Acceptance: spike -> sustained queue-depth breach -> scale_up +
    brownout events -> new replica registers -> post-scale-up TTFT p95
    back under the pre-spike level.  Every request answers 200 (the
    backlog absorbs the spike; the router requeues nothing away) and
    the fleet aggregate reports zero engine restarts."""
    backend = _replica_backend()
    router = ReplicaRouter([], fail_threshold=3, cooldown_secs=2.0,
                           health_interval_secs=1.0,
                           request_timeout_secs=300.0)
    cfg = PolicyConfig(
        ttft_p95_slo_secs=1e9,      # breach on queue depth, not TTFT
        queue_depth_high=4, breach_secs=0.75,
        scale_cooldown_secs=3600.0,  # at most one scale-up
        scale_down_idle_secs=3600.0,
        min_replicas=1, max_replicas=2,
        respawn_backoff_secs=0.5, dead_confirmation_secs=5.0)
    log = tmp_path / "fleet.jsonl"
    sup = FleetSupervisor(router, backend, config=cfg,
                          poll_interval_secs=0.25,
                          event_log_path=str(log))
    srv = None
    try:
        sup.spawn_initial(1)
        sup.start()
        _wait(lambda: router.snapshot()["backends_total"] == 1, 240.0,
              "first replica ready")
        srv, url = _start_router_server(router)

        # spike: a dense 2s burst (~400 arrivals) against a single
        # 4-slot tiny-model replica — the backlog outlives the burst,
        # so the engine queue stays past the breach while it drains
        spike = serve_bench.run_bench(
            url, clients=64, requests=999, tokens=16, stream=True,
            timeout=280.0, seed=11, rate_schedule="1:3,200:2")
        assert spike["errors"] == 0, spike["status_counts"]
        assert set(spike["status_counts"]) == {"200"}

        assert sup.counters["scale_ups_total"] >= 1, \
            "spike never triggered a scale-up"
        _wait(lambda: router.snapshot()["backends_total"] == 2, 240.0,
              "scaled-up replica ready")
        assert router.brownout_remaining() == 0.0   # closed on arrival

        # post-scale-up: the same light load now spreads over 2
        # replicas with an empty queue — p95 TTFT recovers
        calm = serve_bench.run_bench(
            url, clients=4, requests=999, tokens=16, stream=True,
            timeout=280.0, seed=12, rate_schedule="1:6")
        assert calm["errors"] == 0, calm["status_counts"]
        assert calm["ttft_p95_secs"] < spike["ttft_p95_secs"], \
            (calm["ttft_p95_secs"], spike["ttft_p95_secs"])

        # healing never happened and no engine restarted underneath us
        agg = router.aggregated_metrics()["aggregate"]
        assert agg["engine"]["engine_restarts"] == 0
        assert sup.counters["deaths_total"] == 0
        events = [json.loads(l)["event"]
                  for l in log.read_text().splitlines()]
        assert events.count("replica_spawned") == 2
        assert "scale_up" in events and "brownout" in events
    finally:
        if srv is not None:
            srv.shutdown()
            srv.httpd.server_close()
        sup.stop(kill_replicas=True)


def test_sigkill_mid_burst_respawned_and_exactly_once():
    """Acceptance: SIGKILL one of two replicas mid-burst — the router
    fails the in-flight work over (zero drops, one answer per request)
    and the supervisor respawns the dead slot back to a 2-replica
    fleet."""
    import urllib.request

    backend = _replica_backend()
    router = ReplicaRouter([], fail_threshold=2, cooldown_secs=5.0,
                           health_interval_secs=1.0,
                           request_timeout_secs=300.0)
    cfg = PolicyConfig(
        ttft_p95_slo_secs=1e9, queue_depth_high=10 ** 9,
        scale_cooldown_secs=3600.0, scale_down_idle_secs=3600.0,
        min_replicas=2, max_replicas=2,
        respawn_backoff_secs=0.5, dead_confirmation_secs=5.0)
    sup = FleetSupervisor(router, backend, config=cfg,
                          poll_interval_secs=0.5)
    srv = None
    try:
        sup.spawn_initial(2)
        sup.start()
        _wait(lambda: router.snapshot()["backends_total"] == 2, 300.0,
              "both replicas ready")
        srv, url = _start_router_server(router)

        victim_proc = sup.replicas["replica-0"].handle.proc
        n = 24
        results = []
        lock = threading.Lock()
        tail = " ".join(["2"] * 13) + " 3"

        def client(i):
            req = urllib.request.Request(
                url + "/api",
                data=json.dumps({"prompts": [f"{i} {tail}"],
                                 "tokens_to_generate": 16,
                                 "temperature": 0.0,
                                 "no_log": True}).encode(),
                method="PUT")
            with urllib.request.urlopen(req, timeout=280) as resp:
                r = (i, resp.status, json.loads(resp.read()))
            with lock:
                results.append(r)

        def killer():
            time.sleep(1.0)
            victim_proc.send_signal(signal.SIGKILL)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        kt = threading.Thread(target=killer)
        for t in threads:
            t.start()
        kt.start()
        for t in threads:
            t.join(timeout=300)
        kt.join()

        # exactly once: every ticket answered, answered 200, no dupes
        assert sorted(i for i, _, _ in results) == list(range(n))
        assert all(s == 200 for _, s, _ in results)
        assert router.failovers_total >= 1

        # self-healing: the dead slot comes back under its own name
        _wait(lambda: sup.counters["respawns_total"] >= 1, 300.0,
              "respawn of the SIGKILLed replica")
        _wait(lambda: router.snapshot()["backends_total"] == 2, 120.0,
              "respawned replica registered")
        assert sup.counters["deaths_total"] >= 1
        assert sup.replicas["replica-0"].state == "ready"
        names = [e["event"] for e in sup.events]
        assert "replica_died" in names and "replica_respawned" in names
    finally:
        if srv is not None:
            srv.shutdown()
            srv.httpd.server_close()
        sup.stop(kill_replicas=True)


# ---------------------------------------------------------------------------
# zero-recompile guard with the supervisor in the loop
# ---------------------------------------------------------------------------

class _InProcessBackend(ReplicaBackend):
    """Adapter for an already-running in-process server: the supervisor
    exercises its full observe/decide/act loop against it without
    owning a child process."""

    spawn_eta_secs = 1.0

    def __init__(self, url):
        self.url = url

    def spawn(self):
        return object()

    def poll(self, handle):
        return "ready", self.url

    def kill(self, handle):
        pass


def test_supervisor_loop_zero_steady_state_recompiles():
    """Acceptance: the control loop (merged-histogram observation,
    windowed percentiles, policy, brownout bookkeeping) is host-side
    only — with a RecompileDetector armed after warmup, serving through
    a supervised router triggers zero compiles."""
    import jax

    from megatron_llm_tpu import tracing
    from megatron_llm_tpu.models.llama import LlamaModel, llama_config
    from megatron_llm_tpu.serving import EngineConfig, InferenceEngine
    from megatron_llm_tpu.text_generation_server import MegatronServer

    class _Tok:
        vocab_size = 64
        eod = 63
        pad = 0

        def tokenize(self, text):
            return [int(t) % 64 for t in text.split()]

        def detokenize(self, ids):
            return " ".join(str(i) for i in ids)

    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64,
        max_queue_depth=64, default_deadline_secs=0.0))
    eng.warmup()
    eng.start()
    server = MegatronServer(model, params, _Tok(), engine=eng,
                            max_prompts=4, max_tokens=32)
    st = threading.Thread(target=server.run,
                          kwargs={"host": "127.0.0.1", "port": 0},
                          daemon=True)
    st.start()
    for _ in range(200):
        if server.httpd is not None:
            break
        time.sleep(0.05)
    assert server.httpd is not None
    url = f"http://127.0.0.1:{server.httpd.server_address[1]}"

    router = ReplicaRouter([], health_interval_secs=999.0)
    sup = FleetSupervisor(router, _InProcessBackend(url),
                          config=PolicyConfig(
                              ttft_p95_slo_secs=1e9,
                              queue_depth_high=10 ** 9,
                              scale_cooldown_secs=3600.0,
                              scale_down_idle_secs=3600.0,
                              min_replicas=1, max_replicas=1))
    tracer = tracing.SpanTracer()
    det = tracing.RecompileDetector(tracer)
    tracing.install_tracing(tracing.Tracing(tracer=tracer,
                                            recompile=det))
    try:
        sup.spawn_initial(1)
        sup.run_once()
        assert router.snapshot()["backends_total"] == 1
        det.mark_steady()
        for i in range(6):
            status, _, body = router.dispatch(
                "PUT", "/api",
                json.dumps({"prompts": [f"{i} 2 3 4"],
                            "tokens_to_generate": 8,
                            "temperature": 0.0,
                            "no_log": True}).encode())
            assert status == 200, body
            sup.run_once()      # observe (metrics + histograms) + decide
        assert det.recompiles == 0, \
            f"{det.recompiles} recompiles: {list(det.events)}"
    finally:
        tracing.install_tracing(None)
        sup.stop(kill_replicas=False)
        router.stop()
        eng.stop()
        if server.httpd is not None:
            server.httpd.shutdown()
