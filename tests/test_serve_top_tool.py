"""tools/serve_top.py: snapshot building from router-fleet and bare
replica /metrics shapes, frame-delta token rates, the --once/--json CLI
against a canned stdlib stub, and (slow) one live frame from a real
2-replica, 2-router front door."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
import serve_top  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _replica_snap(requests=10, tokens=500, bubble=None):
    """A minimal ServerMetrics.snapshot() twin."""
    snap = {
        "uptime_secs": 60.0, "requests": requests, "errors": 0,
        "tokens_generated": tokens,
        "slo": {"ttft_secs_p95": 0.12, "tpot_secs_p95": 0.034},
        "histograms": {},
        "engine": {
            "queue_depth": 1, "mean_batch_occupancy": 2.5,
            "prefix_cache_hits": 6, "prefix_cache_misses": 2,
            "engine_restarts": 1,
            "cache": {
                "probes": 8, "hits": 6,
                "evictions_capacity": 1, "evictions_churn": 3,
                "ghost": {"x10": {"hit_rate": 0.9}},
                "host_hits": 2,
                "host": {"enabled": 1, "spills_completed": 4},
            },
        },
    }
    if bubble is not None:
        snap["engine"]["loop"] = {
            "wait_pct": round(100.0 - bubble, 3),
            "host_bubble_pct": bubble, "stalls": 2,
        }
    return snap


def _fleet_doc():
    """A router fleet /metrics document: backend_0 healthy, backend_1
    unreachable this probe, backend_2 draining."""
    return {
        "router": {
            "router_id": "r0", "backends_total": 3, "backends_alive": 2,
            "requests_total": 30, "failovers_total": 1,
            "inflight_requests": 4, "brownout_active": True,
            "brownout_remaining_secs": 2.5,
            "backends": {
                "backend_0": {"url": "127.0.0.1:7001", "alive": True,
                              "draining": False},
                "backend_1": {"url": "127.0.0.1:7002", "alive": False,
                              "draining": False},
                "backend_2": {"url": "127.0.0.1:7003", "alive": True,
                              "draining": True},
            },
        },
        "router_tier": {"routers_total": 2, "routers_reporting": 2},
        "aggregate": {"requests": 30},
        "backends": {
            "backend_0": _replica_snap(bubble=35.5),
            "backend_1": None,
            "backend_2": _replica_snap(requests=5, tokens=100),
        },
    }


def test_build_snapshot_router_view():
    snap = serve_top.build_snapshot("http://x", _fleet_doc())
    assert snap["source"] == "router"
    assert snap["router"]["brownout_active"] is True
    assert snap["router_tier"] == {"routers_total": 2,
                                   "routers_reporting": 2}
    rows = {r["name"]: r for r in snap["replicas"]}
    assert set(rows) == {"backend_0", "backend_1", "backend_2"}
    r0 = rows["backend_0"]
    assert r0["alive"] and not r0["draining"]
    assert r0["occupancy"] == 2.5
    assert r0["ttft_p95_secs"] == 0.12
    assert r0["cache_hit_rate"] == pytest.approx(0.75)
    # cache observatory cumulative counters ride into the row; the
    # windowed rates need a previous frame (add_rates)
    assert r0["cache_probes"] == 8 and r0["cache_hits"] == 6
    assert r0["cache_evictions"] == 4
    assert r0["ghost_x10_hit_rate"] == pytest.approx(0.9)
    assert r0["cache_hit_rate_window"] is None
    # host spill tier counters ride into the row; windowed rates need
    # a previous frame too
    assert r0["cache_host_hits"] == 2 and r0["host_spills"] == 4
    assert r0["host_hit_rate_window"] is None
    assert r0["host_spills_per_sec"] is None
    assert r0["host_bubble_pct"] == 35.5
    assert r0["loop_stalls"] == 2
    assert r0["engine_restarts"] == 1
    # unreachable this probe: present, dead, all-None metrics
    assert rows["backend_1"]["alive"] is False
    assert rows["backend_1"]["requests"] is None
    assert rows["backend_2"]["draining"] is True
    # no loop block on backend_2: bubble stays None, row still renders
    assert rows["backend_2"]["host_bubble_pct"] is None
    assert snap["fleet"]["replicas_total"] == 3
    assert snap["fleet"]["replicas_alive"] == 2
    assert snap["fleet"]["tokens_generated"] == 600


def test_build_snapshot_bare_replica_view():
    snap = serve_top.build_snapshot("http://x", _replica_snap(bubble=10.0))
    assert snap["source"] == "replica"
    assert snap["router"] is None
    [row] = snap["replicas"]
    assert row["alive"] and row["host_bubble_pct"] == 10.0


def test_add_rates_from_frame_deltas():
    prev = serve_top.build_snapshot("http://x", _fleet_doc())
    prev["time_unix"] = 100.0
    doc = _fleet_doc()
    doc["backends"]["backend_0"]["tokens_generated"] += 50
    doc["backends"]["backend_2"]["tokens_generated"] += 30
    cache0 = doc["backends"]["backend_0"]["engine"]["cache"]
    cache0["probes"] += 10                  # this frame: 5/10 hit
    cache0["hits"] += 5
    cache0["evictions_churn"] += 6          # 6 evictions / 2s
    cache0["host_hits"] += 3                # this frame: 3/10 host-tier
    cache0["host"]["spills_completed"] += 8  # 8 spills / 2s
    cur = serve_top.build_snapshot("http://x", doc)
    cur["time_unix"] = 102.0
    serve_top.add_rates(cur, prev)
    rows = {r["name"]: r for r in cur["replicas"]}
    assert rows["backend_0"]["tokens_per_sec"] == pytest.approx(25.0)
    assert rows["backend_2"]["tokens_per_sec"] == pytest.approx(15.0)
    assert rows["backend_1"]["tokens_per_sec"] is None
    assert cur["fleet"]["tokens_per_sec"] == pytest.approx(40.0)
    # windowed cache hit rate is THIS frame's delta, not lifetime
    assert rows["backend_0"]["cache_hit_rate_window"] == pytest.approx(0.5)
    assert rows["backend_0"]["evictions_per_sec"] == pytest.approx(3.0)
    assert rows["backend_2"]["cache_hit_rate_window"] is None  # no delta
    assert rows["backend_1"]["evictions_per_sec"] is None
    # host tier: windowed hit share of this frame's probes, spills/sec
    assert rows["backend_0"]["host_hit_rate_window"] == pytest.approx(0.3)
    assert rows["backend_0"]["host_spills_per_sec"] == pytest.approx(4.0)
    assert rows["backend_2"]["host_hit_rate_window"] is None
    assert rows["backend_1"]["host_spills_per_sec"] is None
    # first frame: no previous, rates stay None
    fresh = serve_top.build_snapshot("http://x", _fleet_doc())
    serve_top.add_rates(fresh, {})
    assert fresh["fleet"]["tokens_per_sec"] is None


def test_hist_pct_matches_telemetry_estimator():
    from megatron_llm_tpu import telemetry
    h = telemetry.Histogram((0.1, 0.5, 1.0))
    for v in (0.05, 0.3, 0.3, 0.7, 2.0):
        h.observe(v)
    snap = h.snapshot()
    for q in (0.5, 0.95):
        assert serve_top._hist_pct(snap, q) == pytest.approx(
            telemetry.histogram_percentile(snap, q))
    assert serve_top._hist_pct({}, 0.5) is None
    assert serve_top._hist_pct({"buckets": {}, "count": 0}, 0.5) is None


@pytest.fixture()
def stub_fleet():
    doc = _fleet_doc()

    class H(BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path == "/metrics":
                data = json.dumps(doc).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
            else:
                self.send_response(404)
                self.end_headers()

        def log_message(self, *a):
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()


def test_cli_once_json_against_stub(stub_fleet):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serve_top.py"),
         "--url", stub_fleet, "--once", "--json"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    snap = json.loads(out.stdout)
    assert snap["source"] == "router"
    assert snap["fleet"]["replicas_alive"] == 2
    rows = {r["name"]: r for r in snap["replicas"]}
    assert rows["backend_0"]["host_bubble_pct"] == 35.5


def test_cli_once_table_renders(stub_fleet, capsys):
    assert serve_top.main(["--url", stub_fleet, "--once"]) == 0
    out = capsys.readouterr().out
    assert "replicas 2/3" in out
    assert "routers 2/2" in out
    assert "BROWNOUT" in out
    for col in ("replica", "occ", "tok/s", "ttft_p95", "hit%", "whit%",
                "g10%", "hhit%", "ev/s", "sp/s", "bubble%", "stalls",
                "restarts"):
        assert col in out
    assert "DOWN" in out and "DRAIN" in out


def test_cli_once_fetch_failure_exits_1(capsys):
    # a port nothing listens on: --once reports and exits non-zero
    assert serve_top.main(["--url", "http://127.0.0.1:9",
                           "--once", "--timeout", "0.5"]) == 1
    assert "cannot fetch" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# slow tier: one live frame from a real 2-replica, 2-router front door
# ---------------------------------------------------------------------------

def _spawn_replica(timeout=240.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # single-device child, no 8-dev mesh
    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tests", "_serve_replica.py")],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True, cwd=ROOT)
    deadline = time.monotonic() + timeout
    port = None
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.startswith("PORT "):
            port = int(line.split()[1])
            break
        if proc.poll() is not None:
            raise RuntimeError("replica died during startup")
    assert port, "replica did not report a port in time"
    return proc, port


@pytest.mark.slow
def test_serve_top_once_json_live_router_tier():
    """Acceptance: ``serve_top --once --json`` against one router of a
    live 2-router / 2-replica front door reports both replicas alive
    with engine-loop goodput populated by real traffic."""
    from megatron_llm_tpu.serving import ReplicaRouter, RouterServer

    procs, servers = [], []
    try:
        p0, port0 = _spawn_replica()
        procs.append(p0)
        p1, port1 = _spawn_replica()
        procs.append(p1)
        backends = [f"127.0.0.1:{port0}", f"127.0.0.1:{port1}"]

        def start_router():
            router = ReplicaRouter(backends, health_interval_secs=0.5,
                                   request_timeout_secs=120.0)
            srv = RouterServer(router)
            threading.Thread(target=srv.run,
                             kwargs={"host": "127.0.0.1", "port": 0},
                             daemon=True).start()
            for _ in range(100):
                if srv.httpd is not None:
                    break
                time.sleep(0.05)
            servers.append(srv)
            return router, f"127.0.0.1:{srv.httpd.server_address[1]}"

        router_a, addr_a = start_router()
        router_b, addr_b = start_router()
        router_a.set_peers([addr_b])
        router_b.set_peers([addr_a])
        url = f"http://{addr_a}"

        # real traffic through the front door so loop goodput populates
        # on both replicas (distinct prompts defeat sticky affinity)
        for i in range(8):
            req = urllib.request.Request(
                url + "/api",
                data=json.dumps({"prompts": [f"{i + 1} 2 3 4 5"],
                                 "tokens_to_generate": 8,
                                 "temperature": 0.0,
                                 "no_log": True}).encode(),
                method="PUT")
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.status == 200

        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "serve_top.py"),
             "--url", url, "--once", "--json"],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        snap = json.loads(out.stdout)
        assert snap["source"] == "router"
        assert snap["router_tier"]["routers_total"] == 2
        assert snap["fleet"]["replicas_total"] == 2
        assert snap["fleet"]["replicas_alive"] == 2
        assert snap["fleet"]["requests"] >= 8
        served = [r for r in snap["replicas"] if (r["requests"] or 0) > 0]
        assert served, "no replica reports traffic"
        for row in served:
            assert row["occupancy"] is not None
            assert row["wait_pct"] is not None
            assert row["host_bubble_pct"] == pytest.approx(
                100.0 - row["wait_pct"], abs=0.01)
            assert row["engine_restarts"] == 0
    finally:
        for srv in servers:
            try:
                srv.httpd.shutdown()
            except Exception:
                pass
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
