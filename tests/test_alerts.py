"""SLO sentinel (serving/alerts.py): burn-rate alerting, incident
lifecycle, and postmortem bundles.

Fast tier (tier-1): window/burn-rate arithmetic on an injectable clock,
lifecycle hysteresis/dedup/storm-cap, counter-reset clamping, rule
parsing, the fleet merge, atomic snapshot-bundle writing, the schema-13
``alert_transition`` golden record, the Prometheus ``megatron_alert_
firing`` gauge, and the serve_top/serve_report alert surfaces over
synthesized documents.

Slow tier (``-m slow``; excluded from tier-1):

* chaos e2e — a 2-replica fleet of REAL tiny-model engine subprocesses
  behind the router; faults injected into one replica drive exactly one
  firing -> resolved cycle whose state agrees across the replica
  /metrics, the router's fleet-merged view, the JSONL stream, and
  serve_top, with a readable postmortem bundle on disk and the incident
  rendered by serve_report.
* overhead gate — one full default-rule evaluation over a live engine's
  metrics snapshot must cost < 2% of a measured dispatch.
"""

import contextlib
import io
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from megatron_llm_tpu.serving.alerts import (
    AlertEngine,
    DEFAULT_RULES,
    _frac_over,
    _hist_delta,
    merge_alert_blocks,
    normalize_rule,
    parse_rules_arg,
)

TOOLS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, secs):
        self.t += secs
        return self.t


def _hist(over, under, slo_label="1", over_label="+Inf"):
    """Histogram.snapshot() shape with ``under`` observations in the
    bucket bounded by ``slo_label`` and ``over`` in ``over_label``."""
    return {"buckets": {slo_label: under, over_label: over},
            "count": over + under, "sum": float(over + under)}


def _rate_rule(window=60.0, value=0.05, clear=60.0, for_secs=0.0,
               min_den=1):
    return {"name": "error_rate", "kind": "rate", "num_path": "errors",
            "den_path": "requests", "window_secs": window, "op": ">=",
            "value": value, "min_den": min_den, "for_secs": for_secs,
            "clear_secs": clear, "severity": "page"}


def _threshold_rule(name="qd", path="engine.queue_depth", value=8.0,
                    for_secs=0.0, clear=0.0):
    return {"name": name, "kind": "threshold", "path": path, "op": ">=",
            "value": value, "for_secs": for_secs, "clear_secs": clear,
            "severity": "warn"}


def _burn_rule(**kw):
    rule = {"name": "ttft_burn", "kind": "burn_rate",
            "path": "histograms.ttft_secs", "slo_secs": 1.0,
            "objective": 0.99, "fast_window_secs": 60.0,
            "slow_window_secs": 900.0, "burn_threshold": 14.4,
            "min_count": 20, "for_secs": 0.0, "clear_secs": 0.0,
            "severity": "page"}
    rule.update(kw)
    return rule


# ---------------------------------------------------------------------------
# window + burn arithmetic
# ---------------------------------------------------------------------------

def test_window_sample_requires_full_history():
    """A fresh engine must not false-fire on a partial window: the rate
    rule stays inactive until a ring snapshot is >= window_secs old,
    even while every request is erroring."""
    clock = FakeClock()
    eng = AlertEngine(rules=[_rate_rule(window=60.0)], clock=clock)
    for i in range(5):
        bad = {"errors": i * 2, "requests": i * 2}   # 100% error rate
        assert eng.evaluate(snapshot=bad) == []
        clock.advance(10.0)         # ring spans only 0..40s: no sample
    assert eng.snapshot()["firing"] == []
    clock.advance(25.0)             # oldest entry is now 65s old
    trs = eng.evaluate(snapshot={"errors": 20, "requests": 20})
    assert [t["state"] for t in trs] == ["firing"]
    assert trs[0]["value"] == pytest.approx(1.0)


def test_rate_window_math_on_counter_deltas():
    """The windowed rate is (num delta)/(den delta) between now and the
    newest ring entry at least window_secs old — not lifetime ratios."""
    clock = FakeClock()
    eng = AlertEngine(rules=[_rate_rule(window=30.0, value=0.5,
                                        clear=0.0)], clock=clock)
    eng.evaluate(snapshot={"errors": 100, "requests": 1000})
    clock.advance(31.0)
    # lifetime ratio is 102/1010 ~ 0.1, but the WINDOW saw 2 errors in
    # 10 requests = 0.2 < 0.5: no fire
    assert eng.evaluate(snapshot={"errors": 102, "requests": 1010}) == []
    clock.advance(31.0)
    # window: 8 errors / 10 requests = 0.8 >= 0.5: fire, value = rate
    trs = eng.evaluate(snapshot={"errors": 110, "requests": 1020})
    assert [t["state"] for t in trs] == ["firing"]
    assert trs[0]["value"] == pytest.approx(0.8)
    assert trs[0]["threshold"] == 0.5
    assert trs[0]["window_secs"] == 30.0


def test_rate_counter_reset_clamps_to_empty_window():
    """An engine restart rewinds counters; the delta clamps to the
    post-reset value instead of going negative and must not fire on
    garbage arithmetic."""
    clock = FakeClock()
    eng = AlertEngine(rules=[_rate_rule(window=30.0, value=0.5)],
                      clock=clock)
    eng.evaluate(snapshot={"errors": 50, "requests": 500})
    clock.advance(31.0)
    # restart: counters rewound below the ring sample; deltas read as
    # the raw post-reset values (1 error / 10 requests = 0.1 < 0.5)
    assert eng.evaluate(snapshot={"errors": 1, "requests": 10}) == []


def test_burn_rate_arithmetic_and_two_window_gate():
    """Burn = (windowed fraction over SLO) / error budget, and a page
    needs BOTH the fast and slow windows burning — a brief spike that
    only pollutes the fast window must not fire."""
    clock = FakeClock()
    eng = AlertEngine(rules=[_burn_rule()], clock=clock)
    h0 = _hist(over=0, under=100)
    eng.evaluate(snapshot={"histograms": {"ttft_secs": h0}})
    clock.advance(901.0)            # one sample old enough for BOTH windows
    # 50 of the 100 new observations exceed the 1s SLO: frac 0.5,
    # budget 0.01 -> burn 50 >= 14.4 in both windows -> firing
    h1 = _hist(over=50, under=150)
    trs = eng.evaluate(snapshot={"histograms": {"ttft_secs": h1}})
    assert [t["state"] for t in trs] == ["firing"]
    assert trs[0]["value"] == pytest.approx(50.0)
    assert trs[0]["threshold"] == 14.4

    # fresh engine, same traffic shape but the slow window's sample is
    # missing: strict history means no verdict, no false page
    eng2 = AlertEngine(rules=[_burn_rule()], clock=clock)
    eng2.evaluate(snapshot={"histograms": {"ttft_secs": h0}})
    clock.advance(61.0)             # fast window satisfied, slow not
    assert eng2.evaluate(
        snapshot={"histograms": {"ttft_secs": h1}}) == []


def test_burn_rate_min_count_guard():
    """Tiny windows don't page: fewer than min_count observations in
    either window means no verdict."""
    clock = FakeClock()
    eng = AlertEngine(rules=[_burn_rule(min_count=20)], clock=clock)
    eng.evaluate(snapshot={"histograms": {"ttft_secs": _hist(0, 10)}})
    clock.advance(901.0)
    # only 10 new observations, all over SLO — under min_count
    assert eng.evaluate(snapshot={
        "histograms": {"ttft_secs": _hist(10, 10)}}) == []


def test_frac_over_and_hist_delta_primitives():
    delta = _hist_delta(_hist(over=30, under=70), _hist(over=10, under=50))
    assert delta["count"] == 40
    assert _frac_over(delta, 1.0) == pytest.approx(0.5)
    # +Inf is always bad; a bucket at the SLO bound is good
    assert _frac_over({"buckets": {"1": 5, "+Inf": 5}, "count": 10,
                       "sum": 0.0}, 1.0) == pytest.approx(0.5)
    # reset clamp: negative per-bucket deltas read as zero
    clamped = _hist_delta(_hist(over=0, under=1), _hist(over=10, under=50))
    assert clamped["count"] == 0


# ---------------------------------------------------------------------------
# lifecycle: hysteresis, dedup, storm cap
# ---------------------------------------------------------------------------

def test_hysteresis_pending_firing_resolved():
    clock = FakeClock()
    sink = []
    eng = AlertEngine(rules=[_threshold_rule(value=8.0, for_secs=10.0,
                                             clear=5.0)],
                      clock=clock, transition_sink=sink.append)
    bad = {"engine": {"queue_depth": 20}}
    good = {"engine": {"queue_depth": 1}}
    trs = eng.evaluate(snapshot=bad)
    assert [t["state"] for t in trs] == ["pending"]
    clock.advance(5.0)
    assert eng.evaluate(snapshot=bad) == []       # still pending
    clock.advance(6.0)
    trs = eng.evaluate(snapshot=bad)              # for_secs elapsed
    assert [t["state"] for t in trs] == ["firing"]
    assert eng.snapshot()["firing_count"] == 1
    clock.advance(1.0)
    assert eng.evaluate(snapshot=good) == []      # clear hysteresis starts
    assert eng.snapshot()["firing_count"] == 1    # still firing
    clock.advance(6.0)
    trs = eng.evaluate(snapshot=good)
    assert [t["state"] for t in trs] == ["resolved"]
    assert eng.snapshot()["firing_count"] == 0
    assert [t["state"] for t in sink] == ["pending", "firing", "resolved"]


def test_pending_flap_emits_nothing():
    """pending -> ok (breach vanished before for_secs) is flap noise:
    suppressed entirely, no resolved for something that never fired."""
    clock = FakeClock()
    sink = []
    eng = AlertEngine(rules=[_threshold_rule(for_secs=10.0)],
                      clock=clock, transition_sink=sink.append)
    eng.evaluate(snapshot={"engine": {"queue_depth": 20}})
    clock.advance(2.0)
    assert eng.evaluate(snapshot={"engine": {"queue_depth": 1}}) == []
    assert [t["state"] for t in sink] == ["pending"]
    assert eng.snapshot()["firing_count"] == 0


def test_dedup_steady_breach_single_transition():
    """A breach that persists across many evaluation turns emits ONE
    firing transition — dedup is inherent to the per-rule state."""
    clock = FakeClock()
    sink = []
    eng = AlertEngine(rules=[_threshold_rule()], clock=clock,
                      transition_sink=sink.append)
    for _ in range(10):
        eng.evaluate(snapshot={"engine": {"queue_depth": 20}})
        clock.advance(2.0)
    assert [t["state"] for t in sink] == ["firing"]
    assert eng.counters["transitions_total"] == 1
    assert eng.counters["firing_total"] == 1


def test_storm_cap_suppresses_bundles_not_transitions():
    """When more rules fire than max_firing, the overflow transitions
    still reach the sink (marked storm_suppressed) but skip bundle and
    webhook side effects — an alert storm must not write N bundles."""
    clock = FakeClock()
    sink, bundles = [], []

    def bundle_fn(tr):
        bundles.append(tr["rule"])
        return f"/tmp/{tr['rule']}"

    rules = [_threshold_rule(name=f"r{i:02d}") for i in range(5)]
    eng = AlertEngine(rules=rules, clock=clock, max_firing=3,
                      transition_sink=sink.append, bundle_fn=bundle_fn)
    eng.evaluate(snapshot={"engine": {"queue_depth": 20}})
    assert len(sink) == 5
    suppressed = [t for t in sink if t.get("storm_suppressed")]
    assert len(suppressed) == 2
    assert len(bundles) == 3
    assert eng.counters["storm_suppressed"] == 2
    assert eng.counters["bundles_written"] == 3
    # the capped rules fired without a bundle path
    snap = eng.snapshot()
    assert snap["firing_count"] == 5
    assert sum(1 for f in snap["firing"] if f["bundle"]) == 3


def test_duplicate_rule_names_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        AlertEngine(rules=[_threshold_rule(), _threshold_rule()])


# ---------------------------------------------------------------------------
# rule parsing + fleet merge
# ---------------------------------------------------------------------------

def test_parse_rules_arg_forms(tmp_path):
    rules, opts = parse_rules_arg(json.dumps([_threshold_rule()]))
    assert rules[0]["name"] == "qd" and opts == {}
    rules, opts = parse_rules_arg(json.dumps(
        {"rules": [_rate_rule()], "interval_secs": 0.5, "max_bundles": 2}))
    assert rules[0]["kind"] == "rate"
    assert opts == {"interval_secs": 0.5, "max_bundles": 2}
    # defaults filled by kind
    assert rules[0]["min_den"] == 1
    p = tmp_path / "rules.json"
    p.write_text(json.dumps([_burn_rule()]))
    rules, _ = parse_rules_arg(str(p))
    assert rules[0]["kind"] == "burn_rate"
    with pytest.raises(ValueError, match="unknown kind"):
        parse_rules_arg('[{"name": "x", "kind": "nope"}]')
    with pytest.raises(ValueError, match="unknown op"):
        normalize_rule({"name": "x", "kind": "threshold", "path": "a",
                        "op": "!=", "value": 1})
    with pytest.raises(ValueError, match="missing required"):
        normalize_rule({"name": "x", "kind": "burn_rate", "path": "a"})


def test_default_rules_normalize():
    names = [normalize_rule(r)["name"] for r in DEFAULT_RULES]
    assert len(set(names)) == len(names) == 10


def test_merge_alert_blocks_rewrites_scope_and_sums_counters():
    a = AlertEngine(rules=[_threshold_rule()], scope="replica")
    b = AlertEngine(rules=[_threshold_rule()], scope="replica")
    a.evaluate(snapshot={"engine": {"queue_depth": 20}})
    b.evaluate(snapshot={"engine": {"queue_depth": 1}})
    merged = merge_alert_blocks({"http://a:1": a.snapshot(),
                                 "http://b:2": b.snapshot()})
    assert merged["firing_count"] == 1
    assert merged["firing"][0]["scope"] == "http://a:1"
    assert merged["counters"]["evaluations"] == 2
    assert merged["rules_total"] == 1


# ---------------------------------------------------------------------------
# schema-13 golden record + Prometheus surface
# ---------------------------------------------------------------------------

def test_alert_transition_schema13_golden(tmp_path):
    """Golden record for the alert_transition JSONL contract: changing
    the envelope or payload shape must be a conscious act (update this
    test AND the schema history comment in telemetry.py)."""
    from megatron_llm_tpu import telemetry

    assert telemetry.TELEMETRY_SCHEMA_VERSION == 24
    stream = telemetry.TelemetryStream(str(tmp_path))

    def sink(payload):
        # mirror of the replica wiring in build_server_alerts: the sink
        # stamps kind="serve"; emit() adds schema + time_unix
        stream.emit({"kind": "serve", **payload})

    clock = FakeClock()
    eng = AlertEngine(rules=[_threshold_rule()], clock=clock,
                      transition_sink=sink)
    try:
        eng.evaluate(snapshot={"engine": {"queue_depth": 20}})
    finally:
        stream.close()
    recs = [json.loads(line) for line in
            (tmp_path / "telemetry.jsonl").read_text().splitlines()]
    trs = [r for r in recs if r.get("event") == "alert_transition"]
    assert len(trs) == 1
    rec = trs[0]
    assert frozenset(rec) == frozenset((
        "schema", "kind", "time_unix", "event", "rule", "scope", "state",
        "severity", "value", "threshold", "window_secs", "since_unix",
        "bundle"))
    assert rec["schema"] == telemetry.TELEMETRY_SCHEMA_VERSION
    assert rec["kind"] == "serve"
    assert rec["rule"] == "qd"
    assert rec["scope"] == "replica"
    assert rec["state"] == "firing"
    assert rec["severity"] == "warn"
    assert rec["value"] == 20.0
    assert rec["threshold"] == 8.0
    assert rec["bundle"] is None


def test_prometheus_alert_firing_gauge():
    from megatron_llm_tpu import telemetry

    eng = AlertEngine(rules=[_threshold_rule()])
    eng.evaluate(snapshot={"engine": {"queue_depth": 20}})
    text = telemetry.prometheus_exposition(
        {"requests": 3, "alerts": eng.snapshot()})
    assert ('megatron_alert_firing{rule="qd",scope="replica",'
            'severity="warn"} 1') in text
    assert "# TYPE megatron_alert_firing gauge" in text
    # the non-list alert scalars still walk under the alerts_ prefix
    assert "megatron_serve_alerts_firing_count 1" in text
    assert "megatron_serve_requests 3" in text


def test_snapshot_bundle_atomic_and_bounded(tmp_path):
    from megatron_llm_tpu import telemetry

    dest = str(tmp_path / "incidents" / "rule-0001")
    parts = {"metrics": {"a": 1}, "stacks": "thread dump\n",
             "big": {"blob": "x" * 10000}}
    path = telemetry.write_snapshot_bundle(dest, parts,
                                           max_bytes_per_part=1024,
                                           manifest_extra={"rule": "r"})
    assert path == dest and os.path.isdir(dest)
    man = json.load(open(os.path.join(dest, "manifest.json")))
    assert man["rule"] == "r"
    assert set(man["parts"]) == {"metrics", "stacks", "big"}
    assert man["parts"]["big"]["truncated"] is True
    assert {"metrics.json", "stacks.txt", "big.json",
            "manifest.json"} <= set(os.listdir(dest))
    big = open(os.path.join(dest, "big.json")).read()
    assert len(big.encode()) <= 1024 + 64      # truncation marker slack
    assert "truncated" in big
    # no stray staging dirs, and re-capture into the same name works
    assert os.listdir(str(tmp_path / "incidents")) == ["rule-0001"]
    telemetry.write_snapshot_bundle(dest, {"metrics": {"a": 2}})
    assert json.load(open(os.path.join(dest, "metrics.json")))["a"] == 2


def test_capture_thread_stacks_lists_all_threads():
    from megatron_llm_tpu import telemetry

    ev = threading.Event()
    t = threading.Thread(target=ev.wait, name="stack-probe", daemon=True)
    t.start()
    try:
        text = telemetry.capture_thread_stacks()
    finally:
        ev.set()
        t.join()
    assert "stack-probe" in text
    assert "MainThread" in text


# ---------------------------------------------------------------------------
# tool surfaces over synthesized documents
# ---------------------------------------------------------------------------

def _firing_entry(rule="error_rate", scope="replica", severity="page"):
    return {"rule": rule, "scope": scope, "severity": severity,
            "since_unix": 1.0, "value": 0.5, "threshold": 0.05,
            "window_secs": 60.0, "bundle": None}


def test_serve_top_alert_badges():
    import serve_top as st

    rep = {"requests": 5, "tokens_generated": 10, "histograms": {},
           "alerts": {"firing": [_firing_entry()], "pending": []}}
    snap = st.build_snapshot("http://x", rep)
    assert snap["alerts"]["firing_count"] == 1
    assert snap["replicas"][0]["alert_rules"] == ["error_rate"]
    text = st.render(snap)
    assert "ALERT[1]" in text and "error_rate" in text
    # router doc: replica-merged + supervisor fleet blocks both surface
    doc = {"router": {"router_id": "r0", "brownout_active": False,
                      "backends": {"b0": {"url": "u", "alive": 1}},
                      "fleet": {"alerts": {
                          "firing": [_firing_entry("ttft_burn", "fleet")]}}},
           "aggregate": {"alerts": {"firing": [_firing_entry()]}},
           "backends": {"b0": rep}}
    snap = st.build_snapshot("http://r", doc)
    assert snap["alerts"]["firing_count"] == 2
    assert "ALERT[2]" in st.render(snap)
    # quiet fleet: no badge
    assert "ALERT" not in st.render(
        st.build_snapshot("http://x", {"requests": 1, "histograms": {}}))


def test_serve_report_incident_timeline(tmp_path):
    import serve_report as sr

    recs = [
        {"kind": "serve", "event": "request_done", "e2e_secs": 0.5,
         "ttft_secs": 0.1, "tpot_secs": 0.01, "time_unix": 100.0,
         "finish_reason": "stop"},
        {"kind": "serve", "event": "alert_transition", "schema": 13,
         "rule": "error_rate", "scope": "replica", "state": "firing",
         "severity": "page", "value": 0.5, "threshold": 0.05,
         "window_secs": 60.0, "since_unix": 101.0, "time_unix": 101.0,
         "bundle": "/logs/incidents/error_rate-0001"},
        {"kind": "serve", "event": "engine_restart", "reason": "watchdog",
         "requeued": 2, "failed": 0, "time_unix": 103.0},
        {"kind": "fleet", "event": "replica_died", "slot": 0,
         "time_unix": 104.0},
        {"kind": "serve", "event": "alert_transition", "schema": 13,
         "rule": "error_rate", "scope": "replica", "state": "resolved",
         "severity": "page", "value": 0.0, "threshold": 0.05,
         "window_secs": 60.0, "since_unix": 101.0, "time_unix": 140.0,
         "bundle": None},
    ]
    (tmp_path / "telemetry.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    report = sr.analyze([str(tmp_path)])
    inc = report["incidents"]
    assert inc["transitions"] == {"pending": 0, "firing": 1, "resolved": 1}
    assert inc["unresolved"] == 0
    (incident,) = inc["incidents"]
    assert incident["duration_secs"] == pytest.approx(39.0)
    assert incident["bundle"] == "/logs/incidents/error_rate-0001"
    correlated = {e["event"] for e in incident["correlated"]}
    assert {"engine_restart", "replica_died"} <= correlated
    text = sr.render(report)
    assert "incidents: 1" in text
    assert "error_rate@replica" in text
    assert "engine_restart" in text


def test_serve_bench_slo_gate_exit_code(tmp_path):
    """--slo_gate turns attainment into exit code 3 (distinct from 1 =
    request errors) without touching the happy-path exit codes."""
    import serve_bench as sb

    rows_good = {"slo_joint_attainment": 0.99}
    rows_bad = {"slo_joint_attainment": 0.5}
    # gate arithmetic via the documented JSON keys
    assert set(("ttft_slo_secs", "tpot_slo_secs", "slo_joint_attainment",
                "slo_gate")) <= set(sb.JSON_SCHEMA_KEYS)

    # run_bench against a dead URL: every request errors, attainment 0
    r = sb.run_bench("http://127.0.0.1:1", clients=1, requests=2,
                     tokens=1, timeout=0.2)
    assert r["errors"] == 2
    assert r["slo_joint_attainment"] == 0.0
    assert r["ttft_slo_secs"] == 1.0 and r["tpot_slo_secs"] == 0.25
    rc = sb.main(["--url", "http://127.0.0.1:1", "--clients", "1",
                  "--requests", "1", "--timeout", "0.2", "--json",
                  "--slo_gate", "0.9"])
    assert rc == 3
    rc = sb.main(["--url", "http://127.0.0.1:1", "--clients", "1",
                  "--requests", "1", "--timeout", "0.2", "--json"])
    assert rc == 1
    del rows_good, rows_bad


# ---------------------------------------------------------------------------
# slow tier: chaos e2e + overhead gate
# ---------------------------------------------------------------------------

CHAOS_RULES = json.dumps({
    "interval_secs": 0.25,
    "rules": [{"name": "error_rate", "kind": "rate",
               "num_path": "errors", "den_path": "requests",
               "window_secs": 3.0, "op": ">=", "value": 0.02,
               "min_den": 1, "for_secs": 0.0, "clear_secs": 3.0,
               "severity": "page"}],
})


def _spawn_replica(extra_args=(), timeout=180.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "_serve_replica.py"),
         *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True, cwd=os.path.dirname(os.path.dirname(__file__)))
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


def _get_json(url, timeout=10.0):
    req = urllib.request.Request(url, headers={"Accept": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _generate(url, prompt, tokens=8, timeout=120.0):
    req = urllib.request.Request(
        url + "/api",
        data=json.dumps({"prompts": [prompt], "tokens_to_generate": tokens,
                         "temperature": 0.0, "no_log": True}).encode(),
        method="PUT")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status
    except urllib.error.HTTPError as e:
        e.read()
        return e.code


def _wait(predicate, deadline_secs, what):
    deadline = time.monotonic() + deadline_secs
    while time.monotonic() < deadline:
        v = predicate()
        if v:
            return v
        time.sleep(0.25)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.mark.slow
@pytest.mark.chaos
def test_alert_chaos_two_replica_fleet(tmp_path):
    """Acceptance e2e: nan@/hang@ faults on one replica of a 2-replica
    fleet drive exactly one firing -> resolved incident whose state
    agrees across the replica /metrics, the router's fleet merge, the
    schema-13 JSONL, and serve_top; the postmortem bundle is readable
    on disk; serve_report renders the incident correlated with the
    watchdog engine restart."""
    from megatron_llm_tpu import telemetry
    from megatron_llm_tpu.serving.router import ReplicaRouter, RouterServer
    import serve_top as st
    import serve_report as sr

    log_a = tmp_path / "ra"
    log_b = tmp_path / "rb"
    # replica A: one poisoned dispatch (-> one structured 500) plus one
    # watchdog-length hang (-> one engine restart in the log); alerts on
    pa, port_a = _spawn_replica([
        "--serve_alerts", "1", "--alert_rules", CHAOS_RULES,
        "--structured_log_dir", str(log_a),
        "--serve_fault_inject", "nan@30,hang@60:30",
        "--serve_watchdog_secs", "2.0"])
    pb, port_b = _spawn_replica([
        "--serve_alerts", "1", "--alert_rules", CHAOS_RULES,
        "--structured_log_dir", str(log_b)])
    url_a = f"http://127.0.0.1:{port_a}"
    url_b = f"http://127.0.0.1:{port_b}"
    router = ReplicaRouter([url_a, url_b], fail_threshold=10,
                           cooldown_secs=1.0, health_interval_secs=0.5,
                           request_timeout_secs=120.0)
    srv = RouterServer(router)
    threading.Thread(target=srv.run,
                     kwargs={"host": "127.0.0.1", "port": 0},
                     daemon=True).start()
    try:
        for _ in range(100):
            if srv.httpd is not None:
                break
            time.sleep(0.05)
        router_url = f"http://127.0.0.1:{srv.httpd.server_address[1]}"

        # drive replica A until the poisoned dispatch surfaces as a 500
        def drive_until_error():
            for i in range(8):
                if _generate(url_a, f"{i} 2 3 4") >= 500:
                    return True
            return _get_json(url_a + "/metrics").get("errors", 0) > 0

        assert _wait(drive_until_error, 120.0, "injected nan error")

        # 1) replica /metrics: the alert fires with a bundle on disk
        def replica_firing():
            snap = _get_json(url_a + "/metrics")
            firing = (snap.get("alerts") or {}).get("firing") or []
            return firing[0] if firing else None

        firing = _wait(replica_firing, 30.0, "replica alert firing")
        assert firing["rule"] == "error_rate"

        def bundle_ready():
            f = replica_firing()
            return f and f.get("bundle")

        bundle = _wait(bundle_ready, 15.0, "postmortem bundle path")
        assert os.path.isdir(bundle)
        man = json.load(open(os.path.join(bundle, "manifest.json")))
        assert {"transition", "metrics", "thread_stacks",
                "recent_requests"} <= set(man["parts"])
        stacks = open(os.path.join(bundle, "thread_stacks.txt")).read()
        assert "alert-eval" in stacks
        bundle_metrics = json.load(
            open(os.path.join(bundle, "metrics.json")))
        assert bundle_metrics.get("errors", 0) >= 1

        # 2) fleet merge: the router's aggregate carries the same alert
        #    keyed by the replica's URL
        def router_firing():
            doc = _get_json(router_url + "/metrics")
            firing = ((doc.get("aggregate") or {}).get("alerts")
                      or {}).get("firing") or []
            return [f for f in firing if f["rule"] == "error_rate"]

        merged = _wait(router_firing, 30.0, "fleet-merged alert")
        assert merged[0]["scope"] == url_a

        # 3) serve_top badge agrees (one frame, machine-readable)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = st.main(["--url", router_url, "--once", "--json"])
        assert rc == 0
        frame = json.loads(buf.getvalue())
        assert frame["alerts"]["firing_count"] >= 1
        assert "error_rate" in {f["rule"]
                                for f in frame["alerts"]["firing"]}
        row_a = [r for r in frame["replicas"]
                 if r["url"] == url_a or (r["alive"] and r["alert_rules"])]
        assert any("error_rate" in r["alert_rules"] for r in row_a)

        # 4) healthy traffic pushes the error out of the window; the
        #    hang fires along the way and the watchdog restart heals it
        def drive_and_check_resolved():
            for i in range(4):
                _generate(url_a, f"9{i} 2 3 4")
            snap = _get_json(url_a + "/metrics")
            return not (snap.get("alerts") or {}).get("firing")

        _wait(drive_and_check_resolved, 120.0, "alert resolution")
        assert _get_json(url_a + "/metrics")["engine"][
            "engine_restarts"] >= 1
    finally:
        for proc in (pa, pb):
            proc.kill()
            proc.wait(timeout=30)
        router.stop()
        if srv.httpd is not None:
            srv.httpd.shutdown()

    # 5) JSONL: exactly one firing -> resolved cycle, schema 13
    lines = (log_a / "telemetry.jsonl").read_text().splitlines()
    trs = [json.loads(line) for line in lines
           if '"alert_transition"' in line]
    states = [t["state"] for t in trs if t["rule"] == "error_rate"]
    assert states == ["firing", "resolved"]
    assert all(t["schema"] == telemetry.TELEMETRY_SCHEMA_VERSION
               and t["kind"] == "serve" for t in trs)
    assert trs[0]["bundle"] == bundle

    # 6) serve_report renders the incident, correlated with the restart
    report = sr.analyze([str(log_a)])
    inc = report["incidents"]
    assert inc["transitions"]["firing"] == 1
    assert inc["transitions"]["resolved"] == 1
    assert inc["unresolved"] == 0
    (incident,) = inc["incidents"]
    assert incident["rule"] == "error_rate"
    assert incident["bundle"] == bundle
    assert "engine_restart" in {e["event"]
                                for e in incident["correlated"]}
    text = sr.render(report)
    assert "incidents: 1" in text and "error_rate@replica" in text


@pytest.mark.slow
def test_alert_overhead_under_two_pct_of_dispatch():
    """Overhead gate: one full default-rule evaluation over a live
    engine's /metrics snapshot must cost < 2% of a measured dispatch —
    the sentinel may not become the incident it watches for."""
    import jax
    from megatron_llm_tpu.models.llama import LlamaModel, llama_config
    from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                          SamplingParams)
    from megatron_llm_tpu.text_generation_server import ServerMetrics

    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=8, prefill_chunk=16, max_model_len=64))
    engine.warmup()
    engine.start()
    metrics = ServerMetrics()
    metrics.engine_stats_fn = engine.stats
    engine.request_done_hook = metrics.observe_request_done
    sentinel = AlertEngine(metrics_fn=metrics.snapshot)
    metrics.alert_engine = sentinel
    try:
        reqs = [engine.submit([1 + i % 7, 2, 3],
                              SamplingParams(max_new_tokens=8,
                                             temperature=0.0, eod_id=63))
                for i in range(8)]
        for r in reqs:
            r.result(timeout=180)
        loop = engine.stats()["loop"]
        assert loop["dispatches"] > 0
        mean_dispatch = loop["wall_secs"] / loop["dispatches"]
        for _ in range(50):
            sentinel.evaluate()
        mean_eval = (sentinel.counters["eval_secs_total"]
                     / sentinel.counters["evaluations"])
    finally:
        engine.stop()
    assert mean_eval < 0.02 * mean_dispatch, (
        f"alert evaluation {mean_eval * 1e6:.1f}us vs dispatch "
        f"{mean_dispatch * 1e6:.1f}us: over the 2% budget")
