"""tools/serve_bench.py smoke tests against a canned stdlib HTTP stub —
no model, no jax: the bench must measure and aggregate correctly, and
its CLI must emit the table and --json forms."""

import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
import serve_bench  # noqa: E402


def _start_stub(paged_kernel="xla", prefill_kernel="xla"):
    """Mimics the /api, /api/stream and /metrics contract with canned
    responses (every request generates 3 tokens on a 2-token prompt)."""
    metrics = {"requests": 0, "errors": 0, "throttled": 0}

    class H(BaseHTTPRequestHandler):
        def _json(self, code, body):
            data = json.dumps(body).encode()
            self.send_response(code)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_PUT(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            metrics["requests"] += 1
            if self.path == "/api":
                self._json(200, {"text": ["1 2 9 9 9"],
                                 "tokens": [[1, 2, 9, 9, 9]]})
            elif self.path == "/api/stream":
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.end_headers()
                for t in (9, 9, 9):
                    self.wfile.write(
                        b"data: " + json.dumps({"token": t}).encode()
                        + b"\n\n")
                self.wfile.write(
                    b"data: " + json.dumps(
                        {"done": True, "finish_reason": "length",
                         "tokens": [1, 2, 9, 9, 9]}).encode() + b"\n\n")
            else:
                metrics["errors"] += 1
                self._json(404, {"message": "nope"})

        def do_GET(self):
            if self.path == "/metrics":
                body = dict(metrics)
                # engine counters scale with request count so the bench's
                # prefill/prefix-cache deltas are non-trivial
                n = metrics["requests"]
                body["engine"] = {
                    "prefill_tokens_submitted": 10 * n,
                    "prefill_tokens_computed": 4 * n,
                    "prefill_tokens_cached": 6 * n,
                    "prefix_cache_hits": 2 * n,
                    "prefix_cache_misses": n,
                    "prefix_cache_evictions": 0,
                    "drafted_tokens": 3 * n,
                    "accepted_tokens": 2 * n,
                    "paged_kernel": paged_kernel,
                    "prefill_kernel": prefill_kernel,
                    # loop-goodput counters: 64% dispatch+fetch wait by
                    # construction (0.008 / (0.010 + 0.0025))
                    "loop": {
                        "dispatches": 5 * n,
                        "wall_secs": 0.010 * n,
                        "gap_secs": 0.0025 * n,
                        "wait_secs": 0.008 * n,
                    },
                    # observatory + host spill tier: 2 host-rescued
                    # blocks and 3 device->host spills per request
                    "cache": {
                        "miss_cold": n,
                        "miss_evicted": 0,
                        "evictions_capacity": 0,
                        "evictions_churn": 0,
                        "host_hits": 2 * n,
                        "swap_in_blocks": 2 * n,
                        "host": {
                            "spills_completed": 3 * n,
                            "swap_in_secs": 0.004 * n,
                        },
                    },
                }
                self._json(200, body)
            else:
                self._json(404, {"message": "nope"})

        def log_message(self, *a):
            pass

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


@pytest.fixture()
def stub_server():
    httpd, url = _start_stub()
    yield url
    httpd.shutdown()


def test_run_bench_aggregates(stub_server):
    r = serve_bench.run_bench(stub_server, clients=3, requests=7, tokens=3)
    assert r["requests"] == 7 and r["ok"] == 7 and r["errors"] == 0
    assert r["status_counts"] == {"200": 7}
    assert r["tokens_total"] == 7 * 5
    assert r["tokens_per_sec"] > 0 and r["requests_per_sec"] > 0
    assert r["latency_p50_secs"] is not None
    assert r["latency_p99_secs"] >= r["latency_p95_secs"] \
        >= r["latency_p50_secs"]
    assert r["server_metrics_delta"]["requests"] == 7


def test_run_bench_stream_measures_ttft(stub_server):
    r = serve_bench.run_bench(stub_server, clients=2, requests=4,
                              tokens=3, stream=True)
    assert r["ok"] == 4
    assert r["tokens_total"] == 4 * 3        # streamed tokens only
    assert r["ttft_mean_secs"] is not None and r["ttft_p50_secs"] >= 0
    # TPOT is client-observed inter-token latency, stream-only
    assert r["tpot_mean_secs"] is not None and r["tpot_mean_secs"] >= 0
    assert r["tpot_p95_secs"] >= r["tpot_p50_secs"] >= 0


def test_run_bench_poisson_arrivals(stub_server):
    r = serve_bench.run_bench(stub_server, clients=2, requests=4,
                              tokens=3, rate=200.0)
    assert r["ok"] == 4 and r["rate"] == 200.0


def test_cli_json_and_table(stub_server, capsys):
    rc = serve_bench.main(["--url", stub_server, "--clients", "2",
                           "--requests", "3", "--tokens", "3", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] == 3
    rc = serve_bench.main(["--url", stub_server, "--clients", "2",
                           "--requests", "3", "--tokens", "3"])
    assert rc == 0
    table = capsys.readouterr().out
    assert "latency p95" in table and "throughput" in table


def test_json_schema_keys_always_present(stub_server):
    """Every key in JSON_SCHEMA_KEYS is present in every run_bench
    result (values may be None), so downstream dashboards can rely on
    the shape — this is the documented --json contract."""
    r = serve_bench.run_bench(stub_server, clients=2, requests=3, tokens=3)
    for key in serve_bench.JSON_SCHEMA_KEYS:
        assert key in r, f"missing --json schema key: {key}"
    # and the schema tuple itself has no duplicates
    assert len(set(serve_bench.JSON_SCHEMA_KEYS)) == \
        len(serve_bench.JSON_SCHEMA_KEYS)


def test_build_prompt_shared_prefix():
    # shared-fraction tickets agree on the header, differ in the tail
    a = serve_bench.build_prompt(0, "x", prefix_tokens=16,
                                 shared_prefix_frac=1.0, seed=7)
    b = serve_bench.build_prompt(1, "x", prefix_tokens=16,
                                 shared_prefix_frac=1.0, seed=7)
    assert a != b
    assert a.split()[:16] == b.split()[:16]
    # deterministic per (seed, ticket)
    assert a == serve_bench.build_prompt(0, "x", prefix_tokens=16,
                                         shared_prefix_frac=1.0, seed=7)
    # frac=0: unique same-length header, no sharing
    c = serve_bench.build_prompt(0, "x", prefix_tokens=16,
                                 shared_prefix_frac=0.0, seed=7)
    d = serve_bench.build_prompt(1, "x", prefix_tokens=16,
                                 shared_prefix_frac=0.0, seed=7)
    assert c.split()[:16] != d.split()[:16]
    assert len(c.split()) == len(a.split())
    # prefix_tokens=0 leaves the base prompt untouched
    assert serve_bench.build_prompt(0, "x", prefix_tokens=0,
                                    shared_prefix_frac=1.0, seed=7) == "x"


def test_build_prompt_zipf_skewed_popularity():
    """--prefix_zipf draws the shared header from a pool with Zipf
    popularity: a few hot prefixes dominate, a long tail churns."""
    heads = [serve_bench.build_prompt(
                 t, "x", prefix_tokens=8, shared_prefix_frac=1.0,
                 seed=3, prefix_zipf=1.2, prefix_pool=8).split()[0]
             for t in range(400)]
    counts = {}
    for h in heads:
        counts[h] = counts.get(h, 0) + 1
    assert 1 < len(counts) <= 8                  # a pool, not one prefix
    ranked = sorted(counts.values(), reverse=True)
    assert ranked[0] > 2 * ranked[-1]            # genuinely skewed
    # deterministic per (seed, ticket)
    again = serve_bench.build_prompt(5, "x", prefix_tokens=8,
                                     shared_prefix_frac=1.0, seed=3,
                                     prefix_zipf=1.2, prefix_pool=8)
    assert again == serve_bench.build_prompt(
        5, "x", prefix_tokens=8, shared_prefix_frac=1.0, seed=3,
        prefix_zipf=1.2, prefix_pool=8)
    # zipf ranks are uniform within the header (one prefix per ticket)
    assert len(set(again.split()[:8])) == 1


def test_prefix_workload_reports_engine_deltas(stub_server):
    r = serve_bench.run_bench(stub_server, clients=2, requests=4, tokens=3,
                              prefix_tokens=8, shared_prefix_frac=0.5)
    assert r["prefix_tokens"] == 8
    assert r["shared_prefix_frac"] == 0.5
    # the stub's engine counters advance 10/4/6 per request
    assert r["prefill_tokens_submitted"] == 40
    assert r["prefill_tokens_computed"] == 16
    assert r["prefill_tokens_cached"] == 24
    assert r["prefill_computed_frac"] == pytest.approx(0.4)
    assert r["prefix_cache_hits"] == 8
    assert r["prefix_cache_misses"] == 4
    assert r["prefix_cache_evictions"] == 0
    # computed-prefill throughput = computed delta / wall clock
    assert r["prefill_tokens_per_sec"] > 0
    assert r["prefill_tokens_per_sec"] == pytest.approx(
        16 / r["wall_secs"], rel=0.01)


def test_bench_reports_speculative_deltas(stub_server):
    # the stub's engine drafts 3 and accepts 2 tokens per request
    r = serve_bench.run_bench(stub_server, clients=2, requests=4, tokens=3)
    assert r["drafted_tokens"] == 12
    assert r["accepted_tokens"] == 8
    assert r["accept_rate"] == pytest.approx(8 / 12, abs=1e-4)
    assert r["accepted_tokens_per_sec"] == pytest.approx(
        8 / r["wall_secs"], rel=0.01)


def test_bench_reports_host_tier_deltas(stub_server):
    """The hierarchical-cache keys delta the observatory's two-tier
    attribution counters (cache.host_hits / cache.swap_in_blocks) and
    the spill tier's own sub-block (cache.host.spills_completed /
    swap_in_secs)."""
    r = serve_bench.run_bench(stub_server, clients=2, requests=4, tokens=3)
    assert r["cache_host_hits"] == 8
    assert r["cache_swap_in_blocks"] == 8
    assert r["cache_host_spills"] == 12
    assert r["cache_swap_in_secs"] == pytest.approx(0.016, abs=1e-6)
    assert r["cache_miss_cold"] == 4


def test_bench_reports_loop_goodput_delta(stub_server):
    """wait_pct / host_bubble_pct come from the engine's loop
    counter deltas over the bench window (never from deltaing the
    server's own percentages)."""
    r = serve_bench.run_bench(stub_server, clients=2, requests=4, tokens=3)
    assert r["wait_pct"] == pytest.approx(64.0, abs=0.01)
    assert r["host_bubble_pct"] == pytest.approx(36.0, abs=0.01)


def test_percentile_helper():
    assert serve_bench._percentile([], 0.5) is None
    assert serve_bench._percentile([3.0], 0.99) == 3.0
    vals = [float(i) for i in range(1, 101)]
    assert serve_bench._percentile(vals, 0.50) == pytest.approx(50.0, abs=1)
    assert serve_bench._percentile(vals, 0.95) == pytest.approx(95.0, abs=1)


# ---------------------------------------------------------------------------
# piecewise-rate workloads (--rate_schedule)
# ---------------------------------------------------------------------------

def test_parse_rate_schedule():
    assert serve_bench.parse_rate_schedule("2:1.5, 0:2 ,10:0.5") == \
        [(2.0, 1.5), (0.0, 2.0), (10.0, 0.5)]
    for bad in ("2", "-1:2", "2:0", "2:-1", " , ", "a:b"):
        with pytest.raises(ValueError):
            serve_bench.parse_rate_schedule(bad)


def test_build_arrivals_deterministic_and_segmented():
    sched = serve_bench.parse_rate_schedule("50:1,0:1,200:0.5")
    a = serve_bench.build_arrivals(sched, seed=3)
    assert a == serve_bench.build_arrivals(sched, seed=3)
    assert a != serve_bench.build_arrivals(sched, seed=4)
    ts = [t for t, _ in a]
    assert ts == sorted(ts)
    # arrivals land inside their segment's window; the 0-rate segment
    # is a silent pause (no arrivals at all in [1, 2))
    for t, seg in a:
        assert seg in (0, 2)
        if seg == 0:
            assert 0.0 <= t < 1.0
        else:
            assert 2.0 <= t < 2.5
    assert any(seg == 2 for _, seg in a)


def test_run_bench_rate_schedule_reports_segments(stub_server):
    r = serve_bench.run_bench(stub_server, clients=4, requests=999,
                              tokens=3, seed=5,
                              rate_schedule="30:0.4,0:0.2,80:0.3")
    assert r["rate_schedule"] == "30:0.4,0:0.2,80:0.3"
    segs = r["segments"]
    assert [s["segment"] for s in segs] == [0, 1, 2]
    assert [s["rate"] for s in segs] == [30.0, 0.0, 80.0]
    # request count comes from the schedule, not --requests
    assert r["requests"] == sum(s["requests"] for s in segs)
    assert segs[1]["requests"] == 0          # the silent pause
    for s in segs:
        assert s["ok"] == s["requests"] and s["errors"] == 0
        if s["requests"]:
            assert s["requests_per_sec"] > 0
            assert s["latency_p95_secs"] is not None
    # unscheduled runs keep the keys, valued None (schema stability)
    r2 = serve_bench.run_bench(stub_server, clients=2, requests=3,
                               tokens=3)
    assert r2["rate_schedule"] is None and r2["segments"] is None


def test_cli_rate_schedule_json_and_table(stub_server, capsys):
    rc = serve_bench.main(["--url", stub_server, "--clients", "4",
                           "--tokens", "3", "--rate_schedule",
                           "40:0.3,80:0.2", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert len(out["segments"]) == 2
    rc = serve_bench.main(["--url", stub_server, "--clients", "4",
                           "--tokens", "3", "--rate_schedule",
                           "40:0.3,80:0.2"])
    assert rc == 0
    table = capsys.readouterr().out
    assert "rate schedule" in table


# ---------------------------------------------------------------------------
# kernel A/B (--ab <server_flag>)
# ---------------------------------------------------------------------------

def test_bench_reports_paged_kernel(stub_server):
    r = serve_bench.run_bench(stub_server, clients=2, requests=3, tokens=3)
    assert r["paged_kernel"] == "xla"     # the stub's engine attribution
    assert r["prefill_kernel"] == "xla"


def test_run_ab_tags_arms():
    """run_ab runs the identical workload once per arm and tags every
    row with its arm label plus the server's self-reported attention
    path — the full --json schema holds per row."""
    on_httpd, on_url = _start_stub("pallas")
    off_httpd, off_url = _start_stub("xla")
    try:
        rows = serve_bench.run_ab([on_url, off_url], ["on", "off"],
                                  clients=2, requests=3, tokens=3)
        assert [r["ab_arm"] for r in rows] == ["on", "off"]
        assert [r["paged_kernel"] for r in rows] == ["pallas", "xla"]
        for r in rows:
            assert r["ok"] == 3 and r["errors"] == 0
            for key in serve_bench.JSON_SCHEMA_KEYS:
                assert key in r, f"missing --json schema key: {key}"
    finally:
        on_httpd.shutdown()
        off_httpd.shutdown()


def test_cli_ab_json_and_table(capsys):
    on_httpd, on_url = _start_stub("pallas")
    off_httpd, off_url = _start_stub("xla")
    try:
        rc = serve_bench.main(["--url", on_url, "--ab",
                               "serve_paged_kernel", "--ab_url", off_url,
                               "--clients", "2", "--requests", "3",
                               "--tokens", "3", "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["ab"] == "serve_paged_kernel"
        assert [r["ab_arm"] for r in out["rows"]] == ["on", "off"]
        rc = serve_bench.main(["--url", on_url, "--ab",
                               "serve_paged_kernel", "--ab_url", off_url,
                               "--clients", "2", "--requests", "3",
                               "--tokens", "3"])
        assert rc == 0
        table = capsys.readouterr().out
        assert "serve_paged_kernel=on" in table
        assert "serve_paged_kernel=off" in table
        assert "A/B token throughput" in table
    finally:
        on_httpd.shutdown()
        off_httpd.shutdown()


def test_cli_ab_any_flag_name(capsys):
    """--ab is a free-form server-flag name, not an enum: the prefill
    kernel A/B (and any future boolean flag) reuses the same machinery,
    with the header attributing both attention paths."""
    on_httpd, on_url = _start_stub("xla", prefill_kernel="pallas")
    off_httpd, off_url = _start_stub("xla", prefill_kernel="xla")
    try:
        rc = serve_bench.main(["--url", on_url, "--ab",
                               "serve_prefill_kernel", "--ab_url", off_url,
                               "--clients", "2", "--requests", "3",
                               "--tokens", "3", "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        assert out["ab"] == "serve_prefill_kernel"
        assert [r["prefill_kernel"] for r in out["rows"]] == \
            ["pallas", "xla"]
        rc = serve_bench.main(["--url", on_url, "--ab",
                               "serve_prefill_kernel", "--ab_url", off_url,
                               "--clients", "2", "--requests", "3",
                               "--tokens", "3"])
        assert rc == 0
        table = capsys.readouterr().out
        assert "serve_prefill_kernel=on" in table
        assert "prefill=pallas" in table and "prefill=xla" in table
        assert "A/B prefill throughput" in table
    finally:
        on_httpd.shutdown()
        off_httpd.shutdown()


def test_cli_ab_requires_ab_url():
    with pytest.raises(SystemExit):
        serve_bench.main(["--url", "http://127.0.0.1:1", "--ab",
                          "serve_paged_kernel", "--requests", "1"])


def _spawn_replica(paged_kernel, timeout=240.0, extra_args=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # single-device child, no 8-dev mesh
    here = os.path.dirname(__file__)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(here, "_serve_replica.py"),
         "--paged_kernel", paged_kernel, *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True, cwd=os.path.dirname(here))
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
def test_ab_end_to_end_two_engines(capsys):
    """Acceptance: the one-flag kernel A/B runs end-to-end on CPU — two
    real engine subprocesses (Pallas interpret-mode kernel vs XLA
    gather), one serve_bench invocation, one throughput row per path."""
    p_on, port_on = _spawn_replica("on")
    p_off, port_off = _spawn_replica("off")
    try:
        rc = serve_bench.main([
            "--url", f"http://127.0.0.1:{port_on}",
            "--ab", "serve_paged_kernel",
            "--ab_url", f"http://127.0.0.1:{port_off}",
            "--clients", "2", "--requests", "4", "--tokens", "8",
            "--timeout", "180", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        rows = out["rows"]
        assert [r["ab_arm"] for r in rows] == ["on", "off"]
        assert rows[0]["paged_kernel"] == "pallas"
        assert rows[1]["paged_kernel"] == "xla"
        for r in rows:
            assert r["errors"] == 0 and r["tokens_per_sec"] > 0
    finally:
        for p in (p_on, p_off):
            p.kill()
            p.wait()


@pytest.mark.slow
def test_ab_speculative_end_to_end_two_replicas(capsys):
    """Acceptance: --ab serve_speculative runs end-to-end on CPU — two
    real engine subprocesses (prompt-lookup drafting + K+1 verify step
    vs plain decode), one serve_bench invocation.  The repeated-suffix
    prompt makes bigram lookup land, so the ON arm reports a non-zero
    accept rate; the OFF arm reports zero drafting."""
    p_on, port_on = _spawn_replica(
        "off", extra_args=("--serve_speculative", "1",
                           "--serve_draft_k", "4"))
    p_off, port_off = _spawn_replica("off")
    try:
        rc = serve_bench.main([
            "--url", f"http://127.0.0.1:{port_on}",
            "--ab", "serve_speculative",
            "--ab_url", f"http://127.0.0.1:{port_off}",
            "--clients", "2", "--requests", "4", "--tokens", "12",
            "--prompt", "5 6 7 8 5 6 7 8 5 6 7",
            "--temperature", "0",        # greedy: the drafting mode
            "--timeout", "180", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        rows = out["rows"]
        assert [r["ab_arm"] for r in rows] == ["on", "off"]
        on, off = rows
        for r in rows:
            assert r["errors"] == 0 and r["tokens_per_sec"] > 0
        # greedy spec-on output matches spec-off token-for-token: the
        # stub-free replicas share weights, so identical prompts yield
        # identical throughput-bearing token counts
        assert on["tokens_total"] == off["tokens_total"]
        # the ON arm drafted and accepted on the repeated-suffix prompt
        assert on["drafted_tokens"] > 0
        assert on["accepted_tokens"] > 0
        assert on["accept_rate"] > 0
        assert on["accepted_tokens_per_sec"] > 0
        # the OFF arm never drafts
        assert off["drafted_tokens"] == 0
        assert off["accept_rate"] is None
    finally:
        for p in (p_on, p_off):
            p.kill()
            p.wait()


@pytest.mark.slow
def test_ab_prefill_end_to_end_two_replicas(capsys):
    """Acceptance: --ab serve_prefill_kernel runs end-to-end on CPU —
    two real engine subprocesses (Pallas interpret-mode ragged prefill
    vs XLA dense gather, decode pinned to XLA in both so only prefill
    differs), one serve_bench invocation, per-arm prefill tokens/sec
    and TTFT."""
    p_on, port_on = _spawn_replica(
        "off", extra_args=("--prefill_kernel", "on"))
    p_off, port_off = _spawn_replica(
        "off", extra_args=("--prefill_kernel", "off"))
    try:
        rc = serve_bench.main([
            "--url", f"http://127.0.0.1:{port_on}",
            "--ab", "serve_prefill_kernel",
            "--ab_url", f"http://127.0.0.1:{port_off}",
            "--clients", "2", "--requests", "4", "--tokens", "8",
            "--prompt", "1 2 3 4 5 6 7 8 9 10 11 12",
            "--timeout", "180", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        rows = out["rows"]
        assert [r["ab_arm"] for r in rows] == ["on", "off"]
        assert rows[0]["prefill_kernel"] == "pallas"
        assert rows[1]["prefill_kernel"] == "xla"
        for r in rows:
            assert r["errors"] == 0 and r["tokens_per_sec"] > 0
            # the arm's prompt tokens all ran through chunked prefill
            assert r["prefill_tokens_per_sec"] > 0
            assert r["ttft_mean_secs"] is None or r["ttft_mean_secs"] >= 0
    finally:
        for p in (p_on, p_off):
            p.kill()
            p.wait()


@pytest.mark.slow
def test_ab_host_cache_end_to_end_two_replicas(capsys):
    """Acceptance: --ab serve_host_cache_bytes runs end-to-end on CPU —
    two real engine subprocesses with a 13-block HBM pool (96 cacheable
    tokens) under a Zipf prefix workload whose pool (12 prefixes x 2
    blocks) is twice the HBM budget.  The ON arm rescues evicted
    prefixes from host RAM (host-tier hits, device->host spills); the
    OFF arm recomputes them."""
    p_on, port_on = _spawn_replica(
        "off", extra_args=("--serve_num_blocks", "13",
                           "--serve_host_cache_bytes", str(64 << 20)))
    p_off, port_off = _spawn_replica(
        "off", extra_args=("--serve_num_blocks", "13"))
    try:
        rc = serve_bench.main([
            "--url", f"http://127.0.0.1:{port_on}",
            "--ab", "serve_host_cache_bytes",
            "--ab_url", f"http://127.0.0.1:{port_off}",
            "--clients", "2", "--requests", "32", "--tokens", "4",
            "--prefix_tokens", "16", "--prefix_zipf", "1.0",
            "--prefix_pool", "12", "--shared_prefix_frac", "1.0",
            "--temperature", "0",
            "--timeout", "180", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        rows = out["rows"]
        assert [r["ab_arm"] for r in rows] == ["on", "off"]
        on, off = rows
        for r in rows:
            assert r["errors"] == 0 and r["tokens_per_sec"] > 0
        # the ON arm spilled evicted pages to host RAM and rescued
        # some of them on re-admission
        assert on["cache_host_spills"] > 0
        assert on["cache_host_hits"] > 0
        assert on["cache_swap_in_blocks"] > 0
        assert on["cache_swap_in_secs"] >= 0
        # the OFF arm has no host tier: its counters never move
        assert off["cache_host_hits"] == 0
        assert off["cache_host_spills"] is None
        # host-tier rescues count as prefix-cache hits: the two-tier
        # arm serves at least as many cached prefix blocks
        assert on["prefix_cache_hits"] >= off["prefix_cache_hits"]
    finally:
        for p in (p_on, p_off):
            p.kill()
            p.wait()
