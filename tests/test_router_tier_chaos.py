"""Sharded-front-door chaos e2e (slow tier).

Real processes all the way down: 2 tiny-model engine replicas
(tests/_serve_replica.py) behind 2 ``tools/serve_router.py --dynamic``
router subprocesses, with a live :class:`FleetSupervisor` managing BOTH
tiers through :class:`RouterTierClient`.

The drill: SIGKILL one router mid-burst.

* clients hold the multi-URL list and retry the sibling on a transport
  error — every request answers exactly once;
* the supervisor notices the dead router, emits ``router_died``, and
  respawns it under the same slot (``router_respawned``), peers and
  replica membership resynced;
* the replicas never notice: zero engine restarts, zero deaths — a
  front-door crash is invisible one layer down;
* fleet-wide /metrics keeps answering at the surviving router
  throughout (tier merge degrades to routers_reporting=1, then heals).
"""

import json
import os
import signal
import sys
import threading
import time
from pathlib import Path

import pytest

from megatron_llm_tpu.serving.supervisor import (
    FleetSupervisor,
    LocalProcessBackend,
    PolicyConfig,
    RouterTierClient,
)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
import serve_bench  # noqa: E402

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # single-device children, no 8-dev mesh
    return env


def _replica_backend():
    return LocalProcessBackend(
        [sys.executable, os.path.join(ROOT, "tests", "_serve_replica.py"),
         "--serve_max_queue_depth", "2048",
         "--serve_deadline_secs", "600"],
        env=_child_env(), cwd=ROOT, spawn_eta_secs=90.0)


def _router_backend():
    """Router subprocesses: supervisor-managed membership (--dynamic),
    free ports, fast probing so a killed replica is noticed quickly.
    They speak the same ``PORT <n>`` handshake replicas do."""
    return LocalProcessBackend(
        [sys.executable, os.path.join(ROOT, "tools", "serve_router.py"),
         "--dynamic", "--host", "127.0.0.1", "--port", "0",
         "--probe_interval_secs", "1.0", "--fail_threshold", "2",
         "--breaker_backoff_secs", "5.0"],
        env=_child_env(), cwd=ROOT, spawn_eta_secs=60.0)


def _wait(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.25)
    raise AssertionError(f"timed out waiting for {what}")


def test_router_kill_mid_burst_exactly_once_and_respawn(tmp_path):
    """Acceptance: the front door loses a shard mid-burst and nothing
    above or below it can tell afterwards."""
    client = RouterTierClient()
    cfg = PolicyConfig(
        ttft_p95_slo_secs=1e9, queue_depth_high=10 ** 9,
        scale_cooldown_secs=3600.0, scale_down_idle_secs=3600.0,
        min_replicas=2, max_replicas=2,
        min_routers=2, max_routers=2,
        router_dispatch_p95_slo_secs=1e9, router_inflight_high=10 ** 9,
        respawn_backoff_secs=0.5, dead_confirmation_secs=5.0)
    log = tmp_path / "fleet.jsonl"
    sup = FleetSupervisor(client, _replica_backend(), config=cfg,
                          poll_interval_secs=0.5,
                          event_log_path=str(log),
                          router_backend=_router_backend())
    try:
        sup.spawn_initial(2)
        sup.spawn_initial_routers(2)
        sup.start()

        def tier_ready():
            snaps = [s for s in client.router_snapshots().values()
                     if isinstance(s, dict)]
            return (len(client.routers_list()) == 2 and len(snaps) == 2
                    and all(s.get("backends_alive") == 2 for s in snaps)
                    and all(s.get("peers_total") == 1 for s in snaps))

        _wait(tier_ready, 300.0,
              "2 routers ready, each seeing 2 live replicas + 1 peer")
        urls = sup.router_urls()
        assert len(urls) == 2

        victim_proc = sup.routers["router-0"].handle.proc
        n = 24
        results = []
        lock = threading.Lock()
        tail = " ".join(["2"] * 13) + " 3"

        def one(i):
            # the client half of the crash contract: multi-URL list,
            # round-robin start, retry the sibling on transport error
            r = serve_bench._one_request(
                urls,
                {"prompts": [f"{i} {tail}"], "tokens_to_generate": 16,
                 "temperature": 0.0, "no_log": True},
                stream=False, timeout=280.0, start=i % len(urls))
            with lock:
                results.append((i, r))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n)]
        killer = threading.Timer(
            1.0, lambda: victim_proc.send_signal(signal.SIGKILL))
        for t in threads:
            t.start()
        killer.start()
        for t in threads:
            t.join(timeout=300)
        killer.join()

        # exactly once: every ticket answered, answered 200, no dupes
        assert sorted(i for i, _ in results) == list(range(n))
        bad = [(i, r) for i, r in results if not r["ok"]]
        assert not bad, f"requests failed under router kill: {bad}"
        # ~half the tickets started at the dead router and failed over
        assert sum(r["failovers"] for _, r in results) >= 1
        assert all(r["served_by"] == urls[1]
                   for _, r in results if r["failovers"])

        # the surviving router kept answering fleet /metrics alone...
        m = client.aggregated_metrics()
        assert m.get("aggregate", {}).get("requests", 0) >= n

        # ...and the supervisor healed the slot under its own name
        _wait(lambda: sup.counters["router_respawns_total"] >= 1, 300.0,
              "router respawn")
        _wait(lambda: len(sup.router_urls()) == 2, 120.0,
              "respawned router serving")
        _wait(tier_ready, 120.0, "respawned tier fully rewired")
        assert sup.routers["router-0"].state == "ready"
        assert sup.counters["router_deaths_total"] >= 1

        # the replica tier never noticed the front-door crash
        agg = client.aggregated_metrics()["aggregate"]
        assert agg["engine"]["engine_restarts"] == 0
        assert sup.counters["deaths_total"] == 0
        assert sup.counters["respawns_total"] == 0

        # schema-stamped fleet events tell the whole story
        events = [json.loads(line)
                  for line in log.read_text().splitlines()]
        names = [e["event"] for e in events]
        assert names.count("router_spawned") == 2
        assert "router_died" in names and "router_respawned" in names
        assert all(e.get("schema") is not None for e in events)

        # fleet-wide view from EITHER router now merges both siblings
        # again: histograms bucket-wise, percentiles recomputed
        def tier_merged():
            for url in sup.router_urls():
                snap = client._request(url, "GET", "/metrics")
                tier = (snap or {}).get("router_tier")
                if not tier or tier.get("routers_reporting") != 2:
                    return False
                merged = tier["merged"]
                hist = merged["histograms"]["router_dispatch_secs"]
                # the victim's pre-kill counters died with it; the
                # survivor alone handled at least its own 12 starts
                if hist["count"] < n // 2:
                    return False
                assert merged["slo"]["router_dispatch_secs_p95"] \
                    is not None
            return True

        _wait(tier_merged, 120.0, "tier-merged /metrics at both routers")
    finally:
        sup.stop(kill_replicas=True)
