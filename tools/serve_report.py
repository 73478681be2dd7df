#!/usr/bin/env python
"""Offline analyzer for serving telemetry JSONL (request_done records).

Reads the ``kind: "serve", event: "request_done"`` records a serving
replica writes into its ``--structured_log_dir`` (telemetry schema >= 5:
trace_id, per-request phase attribution, tpot_secs) and prints:

* latency percentiles — e2e / TTFT / TPOT p50/p95/p99 over every
  finished request (the offline twin of the live ``/metrics``
  histograms, but exact: computed from raw values, not buckets)
* a phase breakdown — where request wall-clock went: queue wait,
  admission, prefill compute, amortized decode, stream write; mean
  seconds per request and share of mean e2e latency
* SLO attainment — the fraction of requests meeting configurable TTFT
  (``--ttft_slo``) and TPOT (``--tpot_slo``) targets, individually and
  jointly (the Gemma-on-TPU serving framing: "X% of requests within
  TTFT <= a and TPOT <= b")
* prefill throughput — computed-prefill tokens per second of prefill
  compute, attributed to the attention path (``prefill_kernel``) that
  served them, next to the TTFT numbers it drives
* speculative-decoding summary — fleet accept rate (accepted vs
  drafted tokens, schema >= 8) and mean TPOT for drafting vs plain
  requests: what the PR 14 prompt-lookup speculation bought end-to-end
* cache-hit stratification — the same latency table split by whether
  the request adopted prefix-cache pages (``cached_prompt_tokens > 0``),
  quantifying what the PR 6 prefix cache is worth end-to-end
* engine-loop goodput — ``engine_loop_stats`` rollups (telemetry
  schema >= 10, serving/loop_profiler.py): per-phase share of dispatch
  wall-clock (schedule / draft / build_inputs / dispatch / fetch /
  emit), the share the host waited in dispatch + fetch vs the
  host-bubble percent, the windowed bubble trend, and
  the dispatch-gap stall count — the offline twin of ``/metrics``'
  ``engine.loop`` block; absent (and the report unchanged) on logs
  written before schema 10
* cache observatory — ``cache_stats`` rollups (telemetry schema >= 11,
  serving/cache_observatory.py): the per-prefix heat top-K (salted
  digests only — never token ids), the miss-cause breakdown (cold vs
  evicted-then-wanted-again regret), eviction forensics (capacity vs
  churn), and the ghost capacity projection — per simulated tier
  (2x/4x/10x the block pool) the exact hit rate a bigger cache would
  have had on this trace plus the projected TTFT savings at the log's
  measured prefill throughput; absent on logs before schema 11
* host spill tier — hierarchical KV cache rollups (telemetry schema
  >= 12, serving/host_cache.py): host-tier hit share of the two-tier
  rate, spill/eviction/swap-in volume, the two-tier hit rate compared
  against the ghost projections it realizes, and the TTFT saved per
  request net of the measured host->device swap-in time
* per-replica comparison — pass several JSONL files/dirs (one per
  replica) and each gets its own column plus the fleet total
* fleet-event timeline — supervisor events (``kind: "fleet"``, schema
  >= 7: replica_spawned/died/respawned, scale_up/down, brownout) from a
  serve log or a ``tools/serve_fleet.py --fleet_event_log`` JSONL,
  rendered as counters plus a chronological timeline
* incident timeline — ``alert_transition`` records (telemetry schema
  >= 13, serving/alerts.py) reconstructed into firing->resolved
  incidents, each correlated with the fleet events and engine restarts
  that happened inside its window (±30s) and pointing at its
  postmortem bundle directory

Pure stdlib — no jax import, runs anywhere the files do.

Usage:
    python tools/serve_report.py LOG_DIR_OR_JSONL [more...] \\
        [--ttft_slo SECS] [--tpot_slo SECS] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

STREAM_FILENAME = "telemetry.jsonl"     # mirrors telemetry.STREAM_FILENAME

PHASE_KEYS = ("queue_secs", "admission_secs", "prefill_secs",
              "decode_secs", "stream_write_secs")

# engine-loop host phases; mirrors loop_profiler.LOOP_PHASES (this tool
# must not import jax-adjacent modules)
LOOP_PHASE_KEYS = ("schedule", "draft", "build_inputs", "dispatch", "fetch",
                   "emit")


RESILIENCE_EVENTS = ("engine_restart", "preemption", "drain")

# supervisor control-loop events (kind "fleet", schema >= 7); the order
# here is the counter order in the report
FLEET_EVENTS = ("replica_spawned", "replica_died", "replica_respawned",
                "scale_up", "scale_down", "brownout",
                "router_spawned", "router_died", "router_respawned",
                "router_scale_up", "router_scale_down")


def load_records(path: str) -> List[Dict]:
    """request_done records from a telemetry.jsonl (or its dir)."""
    return _load(path)[0]


def load_resilience_events(path: str) -> List[Dict]:
    """engine_restart / preemption / drain events from a serve log."""
    return _load(path)[1]


def load_fleet_events(path: str) -> List[Dict]:
    """Supervisor fleet events (scale_up / replica_died / ...) from a
    serve log or a --fleet_event_log JSONL."""
    return _load(path)[2]


def load_loop_stats(path: str) -> List[Dict]:
    """engine_loop_stats rollups (telemetry schema >= 10) from a serve
    log, in file order (cumulative per engine lifetime)."""
    return _load(path)[3]


def load_cache_stats(path: str) -> List[Dict]:
    """cache_stats rollups (telemetry schema >= 11) from a serve log,
    in file order (cumulative per engine lifetime)."""
    return _load(path)[4]


def load_alert_transitions(path: str) -> List[Dict]:
    """alert_transition records (telemetry schema >= 13) from a serve
    log — replica-scope (kind serve) and fleet-scope (kind fleet)."""
    return _load(path)[5]


def _load(path: str):
    if os.path.isdir(path):
        path = os.path.join(path, STREAM_FILENAME)
    if not os.path.exists(path):
        raise FileNotFoundError(f"no serve log at {path}")
    records, events, fleet, loop, cache, alerts = [], [], [], [], [], []
    startup = []
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            # alert transitions ride both kinds: "serve" from the
            # replica sentinel, "fleet" from the supervisor's
            # merged-histogram engine (serving/alerts.py)
            if rec.get("event") == "alert_transition":
                alerts.append(rec)
                continue
            if rec.get("kind") == "fleet" \
                    and rec.get("event") in FLEET_EVENTS:
                fleet.append(rec)
                continue
            if rec.get("kind") == "startup":
                startup.append(rec)
            if rec.get("kind") != "serve":
                continue
            if rec.get("event") == "request_done":
                records.append(rec)
            elif rec.get("event") == "engine_loop_stats":
                loop.append(rec)
            elif rec.get("event") == "cache_stats":
                cache.append(rec)
            elif rec.get("event") in RESILIENCE_EVENTS:
                events.append(rec)
    return records, events, fleet, loop, cache, alerts, startup


def _percentile(values: List[float], q: float) -> Optional[float]:
    # nearest-rank with rounding — same estimator as tools/serve_bench.py
    # so the two tools agree on identical samples
    if not values:
        return None
    s = sorted(values)
    return s[min(int(q * (len(s) - 1) + 0.5), len(s) - 1)]


def _vals(records: List[Dict], key: str) -> List[float]:
    return [r[key] for r in records
            if isinstance(r.get(key), (int, float))]


def latency_summary(records: List[Dict]) -> Dict:
    out: Dict[str, object] = {"requests": len(records)}
    for key, name in (("latency_secs", "e2e"), ("ttft_secs", "ttft"),
                      ("tpot_secs", "tpot")):
        vals = _vals(records, key)
        out[f"{name}_mean_secs"] = (sum(vals) / len(vals)
                                    if vals else None)
        for q, tag in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
            out[f"{name}_{tag}_secs"] = _percentile(vals, q)
    return out


def phase_breakdown(records: List[Dict]) -> Dict:
    """Mean seconds per phase and its share of mean e2e latency.  The
    phases need not sum to e2e (decode is amortized; the gap is
    scheduling slack + result pickup), so ``unattributed`` closes the
    account."""
    e2e = _vals(records, "latency_secs")
    mean_e2e = sum(e2e) / len(e2e) if e2e else 0.0
    out: Dict[str, object] = {"mean_e2e_secs": mean_e2e or None}
    attributed = 0.0
    for key in PHASE_KEYS:
        vals = [p[key] for p in (r.get("phases") or {} for r in records)
                if isinstance(p.get(key), (int, float))]
        mean = sum(vals) / len(vals) if vals else 0.0
        attributed += mean
        out[key] = {"mean_secs": mean,
                    "share": (mean / mean_e2e) if mean_e2e else None}
    out["unattributed_secs"] = max(mean_e2e - attributed, 0.0) \
        if mean_e2e else None
    return out


def slo_attainment(records: List[Dict], ttft_slo: float,
                   tpot_slo: float) -> Dict:
    """Fraction of finished requests meeting each target.  A request
    with no measurement for a dimension (e.g. tpot on a 1-token answer)
    counts as meeting it — it cannot have violated it."""
    n = len(records)

    def ok(rec, key, target):
        v = rec.get(key)
        return not isinstance(v, (int, float)) or v <= target

    ttft_ok = sum(ok(r, "ttft_secs", ttft_slo) for r in records)
    tpot_ok = sum(ok(r, "tpot_secs", tpot_slo) for r in records)
    both = sum(ok(r, "ttft_secs", ttft_slo)
               and ok(r, "tpot_secs", tpot_slo) for r in records)
    return {
        "ttft_slo_secs": ttft_slo,
        "tpot_slo_secs": tpot_slo,
        "ttft_attained": (ttft_ok / n) if n else None,
        "tpot_attained": (tpot_ok / n) if n else None,
        "joint_attained": (both / n) if n else None,
    }


def prefill_summary(records: List[Dict]) -> Dict:
    """Computed-prefill throughput: tokens actually pushed through the
    chunked-prefill attention path per second of prefill compute (the
    offline twin of serve_bench's prefill tokens/sec), plus which
    attention path ('pallas'|'xla') served each request so an A/B over
    ``--serve_prefill_kernel`` stays attributable after the fact."""
    toks = sum(r.get("prefill_computed_tokens") or 0 for r in records)
    secs = sum(p["prefill_secs"]
               for p in (r.get("phases") or {} for r in records)
               if isinstance(p.get("prefill_secs"), (int, float)))
    kernels: Dict[str, int] = {}
    for r in records:
        k = r.get("prefill_kernel")
        if k:
            kernels[k] = kernels.get(k, 0) + 1
    # hierarchical KV cache (schema >= 12): blocks served out of host
    # RAM instead of recomputed, and what the swap-in scatters cost
    host_blocks = sum(r.get("host_hit_blocks") or 0 for r in records)
    swap_secs = sum(r.get("swap_in_secs") or 0 for r in records)
    swapping = sum(1 for r in records if (r.get("host_hit_blocks") or 0))
    return {
        "computed_tokens": toks,
        "compute_secs": secs,
        "tokens_per_sec": (toks / secs) if secs > 0 else None,
        "kernel": kernels,
        "host_hit_blocks": host_blocks,
        "swap_in_secs": swap_secs,
        "requests_swapping": swapping,
    }


def speculative_summary(records: List[Dict]) -> Dict:
    """Speculative-decoding effectiveness (telemetry schema >= 8):
    fleet accept rate (total accepted / total drafted), the
    accepted-vs-drafted token totals, how many requests actually
    drafted, and the mean TPOT split by whether the request drafted —
    the offline answer to "what did speculation buy us"."""
    drafted = sum(r.get("drafted_tokens") or 0 for r in records)
    accepted = sum(r.get("accepted_tokens") or 0 for r in records)
    spec = [r for r in records if (r.get("drafted_tokens") or 0) > 0]
    plain = [r for r in records if (r.get("drafted_tokens") or 0) == 0]

    def mean_tpot(rs):
        vals = _vals(rs, "tpot_secs")
        return sum(vals) / len(vals) if vals else None

    return {
        "drafted_tokens": drafted,
        "accepted_tokens": accepted,
        "accept_rate": (accepted / drafted) if drafted > 0 else None,
        "requests_drafting": len(spec),
        "tpot_mean_secs_drafting": mean_tpot(spec),
        "tpot_mean_secs_plain": mean_tpot(plain),
    }


def loop_goodput_summary(per_path: List[List[Dict]]) -> Dict:
    """Engine-loop goodput from ``engine_loop_stats`` rollups: where
    dispatch wall-clock went per host phase, the dispatch + fetch wait
    vs host-bubble percent, the windowed bubble trend, and dispatch-gap
    stall count.

    Rollups are cumulative per engine lifetime, so totals come from
    each log's final record; the trend samples every record's recent
    window (``window.host_bubble_pct``)."""
    totals = {"dispatches": 0, "wall_secs": 0.0, "gap_secs": 0.0,
              "wait_secs": 0.0, "stalls": 0}
    phase_secs = {k: 0.0 for k in LOOP_PHASE_KEYS}
    for recs in per_path:
        if not recs:
            continue
        final = recs[-1]
        for key in totals:
            v = final.get(key)
            if isinstance(v, (int, float)):
                totals[key] += v
        ph = final.get("phase_secs") or {}
        for key in LOOP_PHASE_KEYS:
            if isinstance(ph.get(key), (int, float)):
                phase_secs[key] += ph[key]
    busy = totals["wall_secs"] + totals["gap_secs"]
    wait_pct = (100.0 * min(totals["wait_secs"] / busy, 1.0)
                if busy > 0 else None)
    out: Dict[str, object] = {
        **totals,
        "phase_secs": phase_secs,
        "phase_share": {
            key: (phase_secs[key] / totals["wall_secs"]
                  if totals["wall_secs"] > 0 else None)
            for key in LOOP_PHASE_KEYS},
        "wait_pct": wait_pct,
        "host_bubble_pct": (100.0 - wait_pct
                            if wait_pct is not None else None),
    }
    # windowed host-bubble trend, chronological across all logs
    samples = []
    for recs in per_path:
        for rec in recs:
            b = (rec.get("window") or {}).get("host_bubble_pct")
            if not isinstance(b, (int, float)):
                continue
            t = rec.get("time_unix")
            samples.append((t if isinstance(t, (int, float)) else 0.0, b))
    samples.sort()
    t0 = samples[0][0] if samples else None
    out["bubble_trend"] = [
        {"t_secs": round(t - t0, 3), "host_bubble_pct": round(b, 3)}
        for t, b in samples]
    vals = [b for _, b in samples]
    out["bubble_window_p50_pct"] = _percentile(vals, 0.50)
    out["bubble_window_p95_pct"] = _percentile(vals, 0.95)
    return out


CACHE_COUNTER_KEYS = ("match_calls", "probes", "hits", "misses",
                      "hit_tokens", "miss_cold", "miss_evicted",
                      "evictions_capacity", "evictions_churn",
                      "pool_resets", "inclusion_divergences",
                      "host_hits", "host_hit_tokens", "swap_in_blocks")

# host spill tier counters summed from each log's final cache_stats
# record's ``host`` sub-block (telemetry schema >= 12)
_HOST_TIER_KEYS = ("spills_completed", "spills_dropped", "evictions",
                   "swap_ins", "swap_in_secs")

# heat-table counters summed on fleet merge; mirrors
# serving/cache_observatory.py merge_heat_tops (stdlib re-implementation)
_HEAT_SUM_KEYS = ("hits", "hit_tokens", "residency", "evictions",
                  "regret")


def _merge_heat(tables: List[List[Dict]], k: int = 16) -> List[Dict]:
    merged: Dict[str, Dict] = {}
    for table in tables:
        if not isinstance(table, (list, tuple)):
            continue
        for e in table:
            if not isinstance(e, dict) or "prefix" not in e:
                continue
            cur = merged.get(e["prefix"])
            if cur is None:
                merged[e["prefix"]] = dict(e)
                continue
            for f in _HEAT_SUM_KEYS:
                cur[f] = (cur.get(f) or 0) + (e.get(f) or 0)
            cur["peak_refcount"] = max(cur.get("peak_refcount") or 0,
                                       e.get("peak_refcount") or 0)
    out = sorted(merged.values(),
                 key=lambda e: (-(e.get("hits") or 0)))
    return out[:k]


def cache_observatory_summary(per_path: List[List[Dict]],
                              prefill: Dict,
                              requests: int = 0) -> Dict:
    """Cache observatory rollup from ``cache_stats`` records: counters
    are cumulative per engine lifetime, so totals come from each log's
    final record; heat tables merge by salted prefix (fleet-wide when
    the replicas share MEGATRON_CACHE_SALT).

    The ghost capacity projection prices each simulated tier's extra
    hit tokens at the log's measured prefill throughput: the prefill
    seconds (≈ TTFT) a 2x/4x/10x pool would have saved on this trace."""
    totals = {key: 0 for key in CACHE_COUNTER_KEYS}
    host_totals = {key: 0 for key in _HOST_TIER_KEYS}
    host_enabled = False
    ghost: Dict[str, Dict] = {}
    heat_tables = []
    for recs in per_path:
        if not recs:
            continue
        final = recs[-1]
        for key in CACHE_COUNTER_KEYS:
            v = final.get(key)
            if isinstance(v, (int, float)):
                totals[key] += v
        h = final.get("host")
        if isinstance(h, dict) and h.get("enabled"):
            host_enabled = True
            for key in _HOST_TIER_KEYS:
                v = h.get(key)
                if isinstance(v, (int, float)):
                    host_totals[key] += v
        heat_tables.append(final.get("heat_top") or [])
        for tier, t in (final.get("ghost") or {}).items():
            if not isinstance(t, dict):
                continue
            g = ghost.setdefault(tier, {"hits": 0, "misses": 0,
                                        "hit_tokens": 0, "evictions": 0,
                                        "capacity_blocks": 0})
            for key in g:
                v = t.get(key)
                if isinstance(v, (int, float)):
                    g[key] += v
    probes = totals["probes"]
    out: Dict[str, object] = {
        **totals,
        "hit_rate": (totals["hits"] / probes) if probes else None,
        "heat_top": _merge_heat(heat_tables),
    }
    prefill_tps = (prefill or {}).get("tokens_per_sec")
    tiers = {}
    for tier, g in ghost.items():
        t_probes = g["hits"] + g["misses"]
        extra_tokens = max(g["hit_tokens"] - totals["hit_tokens"], 0)
        saved = (extra_tokens / prefill_tps
                 if prefill_tps else None)
        tiers[tier] = {
            **g,
            "hit_rate": (g["hits"] / t_probes) if t_probes else None,
            "extra_hit_tokens": extra_tokens,
            "prefill_saved_secs_total": saved,
            "ttft_saved_secs_per_request": (
                saved / requests if saved is not None and requests
                else None),
        }
    out["ghost"] = dict(sorted(
        tiers.items(), key=lambda kv: kv[1]["capacity_blocks"]))
    # host spill tier: the realized two-tier rate the ghost tiers only
    # project, with the hit tokens priced at prefill throughput NET of
    # the measured host->device swap-in time (a ghost hit is free; a
    # host hit costs one scatter)
    out["host_tier"] = None
    if host_enabled:
        host_hits = totals["host_hits"]
        saved = (totals["host_hit_tokens"] / prefill_tps
                 if prefill_tps else None)
        net = (saved - host_totals["swap_in_secs"]
               if saved is not None else None)
        out["host_tier"] = {
            **host_totals,
            "hits": host_hits,
            "hit_tokens": totals["host_hit_tokens"],
            "hit_rate": (host_hits / probes) if probes else None,
            "hbm_hit_rate": ((totals["hits"] - host_hits) / probes)
            if probes else None,
            "prefill_saved_secs_total": saved,
            "net_saved_secs_total": net,
            "ttft_saved_secs_per_request": (
                net / requests if net is not None and requests
                else None),
        }
    return out


def cache_stratified(records: List[Dict]) -> Dict:
    hits = [r for r in records
            if (r.get("cached_prompt_tokens") or 0) > 0]
    misses = [r for r in records
              if (r.get("cached_prompt_tokens") or 0) == 0]
    return {"cache_hit": latency_summary(hits),
            "cache_miss": latency_summary(misses)}


def analyze(paths: List[str], ttft_slo: float = 1.0,
            tpot_slo: float = 0.25) -> Dict:
    """Full report over one or more replicas' serve logs."""
    per_replica: Dict[str, Dict] = {}
    all_records: List[Dict] = []
    all_events: List[Dict] = []
    all_fleet: List[Dict] = []
    loop_per_path: List[List[Dict]] = []
    cache_per_path: List[List[Dict]] = []
    all_alerts: List[Dict] = []
    all_startup: List[Dict] = []
    for p in paths:
        records, events, fleet, loop, cache, alerts, startup = _load(p)
        all_startup.extend(startup)
        all_records.extend(records)
        all_events.extend(events)
        all_fleet.extend(fleet)
        loop_per_path.append(loop)
        cache_per_path.append(cache)
        all_alerts.extend(alerts)
        if len(paths) > 1:
            per_replica[p] = {
                **latency_summary(records),
                "slo": slo_attainment(records, ttft_slo, tpot_slo),
            }
    out = {
        "paths": list(paths),
        "summary": latency_summary(all_records),
        "phases": phase_breakdown(all_records),
        "slo": slo_attainment(all_records, ttft_slo, tpot_slo),
        "prefill": prefill_summary(all_records),
        "speculative": speculative_summary(all_records),
        "by_cache": cache_stratified(all_records),
        "finish_reasons": {},
        "traced": sum(1 for r in all_records if r.get("trace_id")),
        # resilience activity over the same window (engine restarts with
        # their requeue/fail split, pool-pressure preemptions, drains,
        # and sentinel slot evictions from the finish_reason stream)
        "resilience": {
            "engine_restarts": sum(
                e.get("event") == "engine_restart" for e in all_events),
            "restart_requeued": sum(
                e.get("requeued") or 0 for e in all_events
                if e.get("event") == "engine_restart"),
            "restart_failed": sum(
                e.get("failed") or 0 for e in all_events
                if e.get("event") == "engine_restart"),
            "preemptions": sum(
                e.get("event") == "preemption" for e in all_events),
            "drains": sum(e.get("event") == "drain" for e in all_events),
            "nonfinite_evictions": sum(
                r.get("finish_reason") == "nonfinite"
                for r in all_records),
        },
    }
    for r in all_records:
        fr = r.get("finish_reason") or "?"
        out["finish_reasons"][fr] = out["finish_reasons"].get(fr, 0) + 1
    if any(loop_per_path):
        # only on schema >= 10 logs; older logs keep the old report shape
        out["loop"] = loop_goodput_summary(loop_per_path)
    if any(cache_per_path):
        # only on schema >= 11 logs (cache observatory)
        out["cache"] = cache_observatory_summary(
            cache_per_path, out["prefill"], requests=len(all_records))
    if all_fleet:
        out["fleet"] = fleet_summary(all_fleet)
    if all_alerts:
        # only on schema >= 13 logs (SLO sentinel, serving/alerts.py)
        out["incidents"] = incident_summary(all_alerts, all_fleet,
                                            all_events)
    if per_replica:
        out["replicas"] = per_replica
    if all_startup:
        # the one ``startup`` record a replica writes at "ready"
        # (tracing.startup_ready): its spans and the compile ledger's sums
        out["startup"] = all_startup
    return out


def incident_summary(transitions: List[Dict], fleet_events: List[Dict],
                     resilience_events: List[Dict],
                     correlate_secs: float = 30.0) -> Dict:
    """Incident lifecycle reconstructed from ``alert_transition``
    records: each firing opens an incident for its (rule, scope), the
    next resolved closes it.  Every incident carries the fleet events
    and engine restarts that happened within ``correlate_secs`` of its
    window — the "what else was going on" a postmortem starts from."""
    transitions = sorted(transitions,
                         key=lambda t: t.get("time_unix") or 0.0)
    counts = {"firing": 0, "resolved": 0, "pending": 0}
    open_by_key: Dict[tuple, Dict] = {}
    incidents: List[Dict] = []
    for tr in transitions:
        state = tr.get("state")
        if state in counts:
            counts[state] += 1
        key = (tr.get("rule"), tr.get("scope"))
        t = tr.get("time_unix")
        if state == "firing":
            inc = {
                "rule": tr.get("rule"),
                "scope": tr.get("scope"),
                "severity": tr.get("severity"),
                "value": tr.get("value"),
                "threshold": tr.get("threshold"),
                "start_unix": t,
                "end_unix": None,
                "duration_secs": None,
                "bundle": tr.get("bundle"),
                "open": True,
            }
            open_by_key[key] = inc
            incidents.append(inc)
        elif state == "resolved" and key in open_by_key:
            inc = open_by_key.pop(key)
            inc["end_unix"] = t
            inc["open"] = False
            if isinstance(t, (int, float)) \
                    and isinstance(inc["start_unix"], (int, float)):
                inc["duration_secs"] = round(t - inc["start_unix"], 3)
    # correlate each incident with concurrent fleet/resilience activity
    context = sorted(
        (e for e in list(fleet_events) + list(resilience_events)
         if isinstance(e.get("time_unix"), (int, float))),
        key=lambda e: e["time_unix"])
    for inc in incidents:
        start = inc.get("start_unix")
        if not isinstance(start, (int, float)):
            inc["correlated"] = []
            continue
        end = inc["end_unix"] if isinstance(inc.get("end_unix"),
                                            (int, float)) else start
        near = []
        for e in context:
            if start - correlate_secs <= e["time_unix"] \
                    <= end + correlate_secs:
                entry = {"event": e.get("event"),
                         "offset_secs": round(e["time_unix"] - start, 3)}
                for key in ("slot", "url", "reason", "requeued",
                            "failed"):
                    if e.get(key) is not None:
                        entry[key] = e[key]
                near.append(entry)
        inc["correlated"] = near
    return {
        "transitions": counts,
        "incidents": incidents,
        "unresolved": sum(1 for i in incidents if i["open"]),
    }


def fleet_summary(events: List[Dict]) -> Dict:
    """Counters plus a chronological timeline of supervisor activity
    (scale-ups, deaths, respawns, brownouts) with offsets relative to
    the first fleet event — the narrative of a chaos/autoscale run."""
    events = sorted(events, key=lambda e: e.get("time_unix") or 0.0)
    t0 = next((e["time_unix"] for e in events
               if isinstance(e.get("time_unix"), (int, float))), None)
    timeline = []
    for e in events:
        t = e.get("time_unix")
        entry = {
            "t_secs": (round(t - t0, 3)
                       if isinstance(t, (int, float)) and t0 is not None
                       else None),
            "event": e.get("event"),
        }
        for key in ("slot", "url", "reason", "exited_while",
                    "ttft_p95_secs", "queue_depth", "eta_secs",
                    "spawn_secs"):
            if e.get(key) is not None:
                entry[key] = e[key]
        timeline.append(entry)
    return {
        "events": {name: sum(e.get("event") == name for e in events)
                   for name in FLEET_EVENTS},
        "timeline": timeline,
    }


def _fmt(v, unit="s") -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.4f}{unit}"
    return f"{v}{unit}"


def _latency_lines(s: Dict, indent: str = "  ") -> List[str]:
    lines = [f"{indent}requests: {s['requests']}"]
    for name in ("e2e", "ttft", "tpot"):
        lines.append(
            f"{indent}{name:>4}  mean {_fmt(s[f'{name}_mean_secs']):>9}"
            f"  p50 {_fmt(s[f'{name}_p50_secs']):>9}"
            f"  p95 {_fmt(s[f'{name}_p95_secs']):>9}"
            f"  p99 {_fmt(s[f'{name}_p99_secs']):>9}")
    return lines


def render(report: Dict) -> str:
    lines = [f"serve_report over {len(report['paths'])} log(s): "
             f"{report['summary']['requests']} requests "
             f"({report['traced']} traced)"]
    lines += _latency_lines(report["summary"])

    ph = report["phases"]
    mean_e2e = ph.get("mean_e2e_secs") or 0.0
    lines.append("\nphase breakdown (mean per request):")
    for key in PHASE_KEYS:
        p = ph[key]
        share = p["share"]
        pct = f"{share * 100:5.1f}%" if share is not None else "    -"
        lines.append(f"  {key:>18} {_fmt(p['mean_secs']):>10} {pct}")
    if ph.get("unattributed_secs") is not None:
        frac = ph["unattributed_secs"] / mean_e2e if mean_e2e else 0.0
        lines.append(f"  {'unattributed':>18} "
                     f"{_fmt(ph['unattributed_secs']):>10} "
                     f"{frac * 100:5.1f}%")

    pf = report.get("prefill") or {}
    if pf.get("computed_tokens"):
        tps = pf.get("tokens_per_sec")
        kern = json.dumps(pf.get("kernel") or {}, sort_keys=True)
        lines.append(f"\nprefill compute: {pf['computed_tokens']} tokens "
                     f"in {_fmt(pf['compute_secs'])} -> "
                     + (f"{tps:.1f} tok/s" if tps else "-")
                     + f" (kernel: {kern})")
        if pf.get("host_hit_blocks"):
            lines.append(
                f"  host swap-ins: {pf['host_hit_blocks']} block(s) "
                f"across {pf['requests_swapping']} request(s) in "
                f"{_fmt(pf['swap_in_secs'])} (prefill skipped, "
                f"scatter paid)")

    sp = report.get("speculative") or {}
    if sp.get("drafted_tokens"):
        rate = sp.get("accept_rate")
        lines.append(
            f"\nspeculative decoding: accepted {sp['accepted_tokens']}/"
            f"{sp['drafted_tokens']} drafted tokens"
            + (f" ({rate * 100:.1f}% accept rate)" if rate is not None
               else "")
            + f" over {sp['requests_drafting']} drafting request(s)")
        lines.append(
            f"  tpot mean  drafting {_fmt(sp['tpot_mean_secs_drafting']):>9}"
            f"  plain {_fmt(sp['tpot_mean_secs_plain']):>9}")

    slo = report["slo"]
    lines.append(f"\nSLO attainment (ttft <= {slo['ttft_slo_secs']}s, "
                 f"tpot <= {slo['tpot_slo_secs']}s):")
    for key in ("ttft_attained", "tpot_attained", "joint_attained"):
        v = slo[key]
        lines.append(f"  {key:>14}: "
                     + (f"{v * 100:.1f}%" if v is not None else "-"))

    lines.append("\nby prefix-cache outcome:")
    for name in ("cache_hit", "cache_miss"):
        s = report["by_cache"][name]
        lines.append(f"  {name} ({s['requests']} requests):")
        if s["requests"]:
            lines += _latency_lines(s, indent="    ")

    if report.get("finish_reasons"):
        lines.append("\nfinish reasons: "
                     + json.dumps(report["finish_reasons"],
                                  sort_keys=True))

    res = report.get("resilience") or {}
    if any(res.values()):
        lines.append("\nresilience activity:")
        for key in ("engine_restarts", "restart_requeued",
                    "restart_failed", "preemptions", "drains",
                    "nonfinite_evictions"):
            lines.append(f"  {key:>20}: {res.get(key, 0)}")

    lp = report.get("loop")
    if lp:
        db, hb = lp.get("wait_pct"), lp.get("host_bubble_pct")
        lines.append(f"\nengine loop goodput "
                     f"({lp['dispatches']} dispatches, "
                     f"{lp['stalls']} stall(s)):")
        lines.append("  dispatch+fetch wait "
                     + (f"{db:.1f}%" if db is not None else "-")
                     + "  host bubble "
                     + (f"{hb:.1f}%" if hb is not None else "-"))
        for key in LOOP_PHASE_KEYS:
            share = lp["phase_share"].get(key)
            pct = f"{share * 100:5.1f}%" if share is not None else "    -"
            lines.append(f"  {key:>18} "
                         f"{_fmt(lp['phase_secs'].get(key)):>10} {pct}")
        trend = lp.get("bubble_trend") or []
        if trend:
            p95 = lp.get("bubble_window_p95_pct")
            lines.append(
                f"  bubble trend: {trend[0]['host_bubble_pct']:.1f}% -> "
                f"{trend[-1]['host_bubble_pct']:.1f}% over "
                f"{len(trend)} window(s)"
                + (f" (window p95 {p95:.1f}%)" if p95 is not None
                   else ""))

    cache = report.get("cache")
    if cache:
        hr = cache.get("hit_rate")
        lines.append(f"\ncache observatory ({cache['probes']} probes, "
                     + (f"{hr * 100:.1f}% hit rate" if hr is not None
                        else "no hit rate") + "):")
        misses = cache.get("misses") or 0
        mc, me = cache.get("miss_cold") or 0, cache.get("miss_evicted") or 0
        lines.append(
            "  miss causes: "
            + (f"cold {mc} ({mc / misses * 100:.1f}%), evicted-then-"
               f"wanted {me} ({me / misses * 100:.1f}%)" if misses
               else "none"))
        lines.append(f"  evictions: capacity {cache['evictions_capacity']}"
                     f", churn {cache['evictions_churn']}"
                     + (f", pool resets {cache['pool_resets']}"
                        if cache.get("pool_resets") else ""))
        heat = cache.get("heat_top") or []
        if heat:
            lines.append("  hottest prefixes (salted digests):")
            lines.append(f"    {'prefix':<18} {'hits':>7} {'tokens':>8} "
                         f"{'peak_rc':>7} {'evict':>6} {'regret':>6}")
            for e in heat[:10]:
                lines.append(
                    f"    {e.get('prefix', '?'):<18} "
                    f"{e.get('hits', 0):>7} "
                    f"{e.get('hit_tokens', 0):>8} "
                    f"{e.get('peak_refcount', 0):>7} "
                    f"{e.get('evictions', 0):>6} "
                    f"{e.get('regret', 0):>6}")
        ghost = cache.get("ghost") or {}
        if ghost:
            lines.append("  capacity projection (ghost tiers — exact "
                         "replay, not an estimate):")
            lines.append(f"    {'tier':<5} {'blocks':>7} {'hit rate':>9} "
                         f"{'extra tok':>10} {'ttft saved/req':>15}")
            for tier, g in ghost.items():
                ghr = g.get("hit_rate")
                saved = g.get("ttft_saved_secs_per_request")
                lines.append(
                    f"    {tier:<5} {g.get('capacity_blocks', 0):>7} "
                    + (f"{ghr * 100:>8.1f}%" if ghr is not None
                       else f"{'-':>9}")
                    + f" {g.get('extra_hit_tokens', 0):>10} "
                    + (f"{saved:>14.4f}s" if saved is not None
                       else f"{'-':>15}"))
        host = cache.get("host_tier")
        if host:
            lines.append(
                f"  host spill tier: {host['hits']} hit(s) "
                f"({host['hit_rate'] * 100:.1f}% of probes)"
                if host.get("hit_rate") is not None else
                f"  host spill tier: {host['hits']} hit(s)")
            lines.append(
                f"    spills {host['spills_completed']} "
                f"(dropped {host['spills_dropped']}, "
                f"evicted {host['evictions']}), swap-ins "
                f"{host['swap_ins']} in {_fmt(host['swap_in_secs'])}")
            # the realized-vs-projected line: the ghost tiers say what
            # a bigger HBM pool WOULD hit; the host tier is the tier we
            # actually bought — compare the two-tier rate against each
            # projection
            two_tier = cache.get("hit_rate")
            if two_tier is not None and ghost:
                proj = " ".join(
                    f"{t}={g['hit_rate'] * 100:.1f}%"
                    for t, g in ghost.items()
                    if g.get("hit_rate") is not None)
                if proj:
                    lines.append(
                        f"    two-tier hit rate {two_tier * 100:.1f}% "
                        f"vs ghost projection {proj}")
            net = host.get("ttft_saved_secs_per_request")
            if net is not None:
                lines.append(
                    f"    ttft saved/req {net:.4f}s "
                    f"(net of measured swap-in time)")

    fleet = report.get("fleet")
    if fleet:
        counts = " ".join(f"{k}={v}" for k, v in fleet["events"].items()
                          if v)
        lines.append(f"\nfleet events: {counts or '-'}")
        for e in fleet["timeline"]:
            t = e.get("t_secs")
            detail = " ".join(
                f"{k}={e[k]}" for k in ("slot", "url", "reason",
                                        "exited_while", "ttft_p95_secs",
                                        "queue_depth", "eta_secs",
                                        "spawn_secs") if k in e)
            lines.append(f"  +{t if t is not None else '?':>9}s "
                         f"{e['event']:<18} {detail}")

    inc = report.get("incidents")
    if inc:
        tr = inc["transitions"]
        lines.append(f"\nincidents: {len(inc['incidents'])} "
                     f"({inc['unresolved']} unresolved; transitions: "
                     f"pending {tr['pending']}, firing {tr['firing']}, "
                     f"resolved {tr['resolved']})")
        for i in inc["incidents"]:
            dur = (f"{i['duration_secs']:.1f}s"
                   if i.get("duration_secs") is not None
                   else "OPEN")
            lines.append(
                f"  [{i.get('severity', '?'):<4}] {i.get('rule')}"
                f"@{i.get('scope')}  {dur}"
                + (f"  value {i['value']:.4g}"
                   f" (threshold {i['threshold']:.4g})"
                   if isinstance(i.get("value"), (int, float))
                   and isinstance(i.get("threshold"), (int, float))
                   else ""))
            if i.get("bundle"):
                lines.append(f"         bundle: {i['bundle']}")
            for e in i.get("correlated", [])[:8]:
                detail = " ".join(
                    f"{k}={e[k]}" for k in ("slot", "url", "reason",
                                            "requeued", "failed")
                    if k in e)
                lines.append(f"         {e['offset_secs']:+9.1f}s "
                             f"{e['event']:<18} {detail}")

    for path, s in (report.get("replicas") or {}).items():
        lines.append(f"\nreplica {path} "
                     f"(joint SLO "
                     + (f"{s['slo']['joint_attained'] * 100:.1f}%"
                        if s['slo']['joint_attained'] is not None
                        else "-") + "):")
        lines += _latency_lines(s)

    for st in report.get("startup") or []:
        spans = " ".join(f"{n} {v:.2f}" for n, v in st["spans"].items())
        kinds = " ".join(f"{k} {v:.2f}"
                         for k, v in st["compile_secs"].items())
        lines.append(f"\nstart-up: ready after {st['wall_secs']:.2f}s | "
                     f"spans (s): {spans}")
        lines.append(f"  compile union (s): {kinds}")
        for p in st.get("top_programs") or []:
            lines.append(f"  {p['trace_lower_secs']:8.2f}s trace + lower  "
                         f"{p['program']} (traced x{p['traced']}, lowered "
                         f"x{p['lowered']})")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarize serving request_done telemetry")
    ap.add_argument("paths", nargs="+", metavar="path",
                    help="telemetry.jsonl file(s) or --structured_log_dir "
                         "dir(s); several -> per-replica comparison")
    ap.add_argument("--ttft_slo", type=float, default=1.0,
                    help="time-to-first-token target in seconds")
    ap.add_argument("--tpot_slo", type=float, default=0.25,
                    help="time-per-output-token target in seconds")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object")
    args = ap.parse_args(argv)

    try:
        report = analyze(args.paths, ttft_slo=args.ttft_slo,
                         tpot_slo=args.tpot_slo)
    except FileNotFoundError as e:
        print(str(e), file=sys.stderr)
        return 2
    if report["summary"]["requests"] == 0 and not report.get("fleet"):
        print("no request_done records found (serve with "
              "--structured_log_dir and schema >= 5)", file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(render(report))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:         # e.g. piped into head
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
