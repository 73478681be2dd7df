#!/usr/bin/env python3
"""serve_top: live terminal flight deck for a serving fleet.

Polls one endpoint's ``GET /metrics`` — point it at any router of a
sharded front door for the peer-merged fleet view, or directly at a
single replica — and renders a refreshing per-replica table:
occupancy, tokens/sec, TTFT/TPOT p95, prefix-cache hit rate (lifetime
and frame-windowed), the ghost x10 projected hit rate and evictions/sec
from the cache observatory (serving/cache_observatory.py), the
windowed host-tier hit rate and device->host spills/sec from the
hierarchical KV cache (serving/host_cache.py), the engine-loop ``host
bubble %`` (serving/loop_profiler.py), engine restarts, router
brownout state, and ALERT badges from the SLO sentinel
(serving/alerts.py): per-replica firing rules in the table, the
fleet-wide union (replica-merged + supervisor fleet scope) in the
header line.

Stdlib only (no jax, no requests): runs on a laptop against a tunnel,
like serve_bench / serve_report.

    python tools/serve_top.py --url http://localhost:8000
    python tools/serve_top.py --url http://localhost:8000 --once --json

``--once`` prints a single snapshot and exits (with ``--json``, one
machine-readable object — what the tests consume).  Tokens/sec needs
two polls, so it is null on the first frame and in ``--once`` mode.
"""

import argparse
import json
import sys
import time
import urllib.error
import urllib.request


def fetch_metrics(url: str, timeout: float) -> dict:
    req = urllib.request.Request(
        url.rstrip("/") + "/metrics",
        headers={"Accept": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8", "replace"))


def _hist_pct(snap, q):
    """Percentile from a Histogram.snapshot() shape (linear
    interpolation in the winning bucket — the telemetry.py estimator,
    re-implemented here so this tool stays stdlib-only)."""
    if not (isinstance(snap, dict) and isinstance(snap.get("buckets"), dict)):
        return None
    total = snap.get("count") or 0
    if total <= 0:
        return None
    items = []
    for k, v in snap["buckets"].items():
        bound = float("inf") if k in ("+Inf", "inf") else float(k)
        items.append((bound, int(v)))
    items.sort()
    target = max(min(float(q), 1.0), 0.0) * total
    cum, lo = 0, 0.0
    for bound, c in items:
        if c > 0 and cum + c >= target:
            if bound == float("inf"):
                return lo
            frac = (target - cum) / c if c else 1.0
            return lo + (bound - lo) * max(min(frac, 1.0), 0.0)
        cum += c
        if bound != float("inf"):
            lo = bound
    return lo


def _num(d, *path):
    """Nested numeric lookup; None on any missing/non-numeric hop."""
    cur = d
    for p in path:
        if not isinstance(cur, dict):
            return None
        cur = cur.get(p)
    if isinstance(cur, bool) or not isinstance(cur, (int, float)):
        return None
    return cur


def _replica_row(name: str, url, snap) -> dict:
    """One table row from a replica's ServerMetrics snapshot (None when
    the router could not reach it this probe)."""
    row = {
        "name": name,
        "url": url,
        "alive": snap is not None,
        "requests": None, "tokens_generated": None,
        "tokens_per_sec": None,
        "occupancy": None, "queue_depth": None,
        "ttft_p95_secs": None, "tpot_p95_secs": None,
        "cache_hit_rate": None,
        "cache_probes": None, "cache_hits": None,
        "cache_hit_rate_window": None,
        "cache_evictions": None, "evictions_per_sec": None,
        "ghost_x10_hit_rate": None,
        "cache_host_hits": None, "host_spills": None,
        "host_hit_rate_window": None, "host_spills_per_sec": None,
        "wait_pct": None, "host_bubble_pct": None,
        "loop_stalls": None, "engine_restarts": None,
        "draining": False,
        "alerts_firing": None, "alert_rules": [],
    }
    if snap is None:
        return row
    ab = snap.get("alerts")
    if isinstance(ab, dict) and isinstance(ab.get("firing"), list):
        rules = [f.get("rule") for f in ab["firing"]
                 if isinstance(f, dict) and f.get("rule")]
        row["alerts_firing"] = len(rules)
        row["alert_rules"] = rules
    row["requests"] = _num(snap, "requests")
    row["tokens_generated"] = _num(snap, "tokens_generated")
    row["ttft_p95_secs"] = (
        _num(snap, "slo", "ttft_secs_p95")
        if _num(snap, "slo", "ttft_secs_p95") is not None
        else _hist_pct((snap.get("histograms") or {}).get("ttft_secs"),
                       0.95))
    row["tpot_p95_secs"] = (
        _num(snap, "slo", "tpot_secs_p95")
        if _num(snap, "slo", "tpot_secs_p95") is not None
        else _hist_pct((snap.get("histograms") or {}).get("tpot_secs"),
                       0.95))
    eng = snap.get("engine")
    if isinstance(eng, dict):
        row["occupancy"] = _num(eng, "mean_batch_occupancy")
        row["queue_depth"] = _num(eng, "queue_depth")
        hits = _num(eng, "prefix_cache_hits") or 0
        misses = _num(eng, "prefix_cache_misses") or 0
        if hits + misses > 0:
            row["cache_hit_rate"] = round(hits / (hits + misses), 4)
        row["wait_pct"] = _num(eng, "loop", "wait_pct")
        row["host_bubble_pct"] = _num(eng, "loop", "host_bubble_pct")
        row["loop_stalls"] = _num(eng, "loop", "stalls")
        row["engine_restarts"] = _num(eng, "engine_restarts")
        # cache observatory block (serving/cache_observatory.py):
        # cumulative counters here; the windowed rates come from frame
        # deltas in add_rates
        row["cache_probes"] = _num(eng, "cache", "probes")
        row["cache_hits"] = _num(eng, "cache", "hits")
        ec = _num(eng, "cache", "evictions_capacity")
        eh = _num(eng, "cache", "evictions_churn")
        if ec is not None or eh is not None:
            row["cache_evictions"] = (ec or 0) + (eh or 0)
        row["ghost_x10_hit_rate"] = _num(eng, "cache", "ghost", "x10",
                                         "hit_rate")
        # hierarchical KV cache: host-tier rescues out of the two-tier
        # hit attribution, device->host spills from the tier itself
        row["cache_host_hits"] = _num(eng, "cache", "host_hits")
        row["host_spills"] = _num(eng, "cache", "host",
                                  "spills_completed")
    return row


def build_snapshot(url: str, metrics: dict) -> dict:
    """Reduce one /metrics document (router fleet view or a bare
    replica snapshot) to the flight-deck schema."""
    out = {
        "time_unix": time.time(),
        "url": url,
        "source": "router" if "router" in metrics else "replica",
        "router": None,
        "router_tier": None,
        "replicas": [],
    }
    if out["source"] == "router":
        rsnap = metrics.get("router") or {}
        out["router"] = {
            "router_id": rsnap.get("router_id"),
            "backends_total": _num(rsnap, "backends_total"),
            "backends_alive": _num(rsnap, "backends_alive"),
            "requests_total": _num(rsnap, "requests_total"),
            "failovers_total": _num(rsnap, "failovers_total"),
            "inflight_requests": _num(rsnap, "inflight_requests"),
            "brownout_active": bool(rsnap.get("brownout_active")),
            "brownout_remaining_secs": _num(
                rsnap, "brownout_remaining_secs"),
        }
        tier = metrics.get("router_tier")
        if isinstance(tier, dict):
            out["router_tier"] = {
                "routers_total": _num(tier, "routers_total"),
                "routers_reporting": _num(tier, "routers_reporting"),
            }
        meta = rsnap.get("backends") or {}
        snaps = metrics.get("backends") or {}
        for name in sorted(set(meta) | set(snaps),
                           key=lambda n: (len(n), n)):
            m = meta.get(name) or {}
            row = _replica_row(name, m.get("url"), snaps.get(name))
            if m.get("draining"):
                row["draining"] = True
            if not m.get("alive", 1):
                row["alive"] = False
            out["replicas"].append(row)
    else:
        out["replicas"].append(_replica_row("replica_0", url, metrics))
    # alert rollup (serving/alerts.py): replica alerts fleet-merged by
    # the router under aggregate.alerts, the supervisor's own fleet-scope
    # engine under router.fleet.alerts; a bare replica carries its block
    # at top level.  The ALERT badge unions all of them.
    firing = []
    blocks = []
    if out["source"] == "router":
        agg = metrics.get("aggregate")
        if isinstance(agg, dict):
            blocks.append(agg.get("alerts"))
        fl = (metrics.get("router") or {}).get("fleet")
        if isinstance(fl, dict):
            blocks.append(fl.get("alerts"))
    else:
        blocks.append(metrics.get("alerts"))
    for ab in blocks:
        if isinstance(ab, dict) and isinstance(ab.get("firing"), list):
            for f in ab["firing"]:
                if isinstance(f, dict) and f.get("rule"):
                    firing.append({"rule": f.get("rule"),
                                   "scope": f.get("scope"),
                                   "severity": f.get("severity")})
    out["alerts"] = {"firing": firing, "firing_count": len(firing)}
    alive = [r for r in out["replicas"] if r["alive"]]
    out["fleet"] = {
        "replicas_total": len(out["replicas"]),
        "replicas_alive": len(alive),
        "requests": sum(r["requests"] or 0 for r in alive),
        "tokens_generated": sum(r["tokens_generated"] or 0 for r in alive),
        "tokens_per_sec": None,
    }
    return out


def add_rates(snapshot: dict, prev: dict) -> None:
    """Fill per-replica and fleet tokens/sec from the previous frame's
    (time, tokens) pairs; mutates ``snapshot`` in place."""
    if not prev:
        return
    dt = snapshot["time_unix"] - prev.get("time_unix", 0)
    if dt <= 0:
        return
    prev_rows = {r["name"]: r for r in prev.get("replicas", [])}
    fleet_rate = 0.0
    any_rate = False
    for row in snapshot["replicas"]:
        p = prev_rows.get(row["name"])
        if (p is None or row["tokens_generated"] is None
                or p.get("tokens_generated") is None):
            continue
        rate = max(row["tokens_generated"] - p["tokens_generated"], 0) / dt
        row["tokens_per_sec"] = round(rate, 2)
        fleet_rate += rate
        any_rate = True
    for row in snapshot["replicas"]:
        p = prev_rows.get(row["name"])
        if p is None:
            continue
        # windowed cache hit rate: hits/probes over this frame only
        if (row["cache_probes"] is not None
                and p.get("cache_probes") is not None):
            dp = row["cache_probes"] - p["cache_probes"]
            dh = (row["cache_hits"] or 0) - (p.get("cache_hits") or 0)
            if dp > 0:
                row["cache_hit_rate_window"] = round(
                    max(min(dh / dp, 1.0), 0.0), 4)
        if (row["cache_evictions"] is not None
                and p.get("cache_evictions") is not None):
            row["evictions_per_sec"] = round(
                max(row["cache_evictions"] - p["cache_evictions"], 0) / dt,
                2)
        # windowed host-tier hit rate: host-rescued blocks / probes
        # over this frame only (lifetime counters mask regressions)
        if (row["cache_probes"] is not None
                and p.get("cache_probes") is not None
                and row["cache_host_hits"] is not None
                and p.get("cache_host_hits") is not None):
            dp = row["cache_probes"] - p["cache_probes"]
            dh = row["cache_host_hits"] - p["cache_host_hits"]
            if dp > 0:
                row["host_hit_rate_window"] = round(
                    max(min(dh / dp, 1.0), 0.0), 4)
        if (row["host_spills"] is not None
                and p.get("host_spills") is not None):
            row["host_spills_per_sec"] = round(
                max(row["host_spills"] - p["host_spills"], 0) / dt, 2)
    if any_rate:
        snapshot["fleet"]["tokens_per_sec"] = round(fleet_rate, 2)


def _fmt(v, spec="", dash="-"):
    if v is None:
        return dash
    try:
        return format(v, spec)
    except (TypeError, ValueError):
        return str(v)


COLUMNS = (
    # header, width, row key, format spec
    ("replica", 12, "name", ""),
    ("up", 4, None, ""),
    ("occ", 6, "occupancy", ".2f"),
    ("queue", 6, "queue_depth", "d"),
    ("tok/s", 9, "tokens_per_sec", ".1f"),
    ("ttft_p95", 9, "ttft_p95_secs", ".3f"),
    ("tpot_p95", 9, "tpot_p95_secs", ".4f"),
    ("hit%", 7, None, ""),
    ("whit%", 7, None, ""),
    ("g10%", 6, None, ""),
    ("hhit%", 7, None, ""),
    ("ev/s", 6, "evictions_per_sec", ".1f"),
    ("sp/s", 6, "host_spills_per_sec", ".1f"),
    ("bubble%", 8, "host_bubble_pct", ".1f"),
    ("stalls", 7, "loop_stalls", "d"),
    ("restarts", 8, "engine_restarts", "d"),
    ("alerts", 16, None, ""),
)


def render(snapshot: dict) -> str:
    lines = []
    r = snapshot.get("router")
    tier = snapshot.get("router_tier")
    fleet = snapshot["fleet"]
    head = (f"serve_top  {snapshot['url']}  "
            f"replicas {fleet['replicas_alive']}/{fleet['replicas_total']}")
    if tier:
        head += (f"  routers {_fmt(tier['routers_reporting'])}"
                 f"/{_fmt(tier['routers_total'])}")
    if r:
        head += f"  inflight {_fmt(r['inflight_requests'])}"
        if r["brownout_active"]:
            head += (f"  BROWNOUT "
                     f"({_fmt(r['brownout_remaining_secs'], '.1f')}s)")
    al = snapshot.get("alerts") or {}
    if al.get("firing_count"):
        rules = sorted({f["rule"] for f in al["firing"]})
        head += (f"  ALERT[{al['firing_count']}] "
                 + ",".join(rules[:4])
                 + ("…" if len(rules) > 4 else ""))
    head += (f"  fleet {_fmt(fleet['tokens_per_sec'], '.1f')} tok/s"
             f"  {time.strftime('%H:%M:%S')}")
    lines.append(head)
    lines.append("")
    lines.append("  ".join(h.ljust(w) for h, w, _, _ in COLUMNS))
    for row in snapshot["replicas"]:
        cells = []
        for h, w, key, spec in COLUMNS:
            if h == "up":
                v = ("DRAIN" if row["draining"]
                     else "up" if row["alive"] else "DOWN")
            elif h == "alerts":
                v = (",".join(row["alert_rules"])[:15]
                     if row["alert_rules"] else "-")
            elif h in ("hit%", "whit%", "g10%", "hhit%"):
                hr = row[{"hit%": "cache_hit_rate",
                          "whit%": "cache_hit_rate_window",
                          "g10%": "ghost_x10_hit_rate",
                          "hhit%": "host_hit_rate_window"}[h]]
                v = _fmt(100.0 * hr, ".1f") if hr is not None else "-"
            else:
                v = _fmt(row.get(key), spec)
            cells.append(str(v).ljust(w))
        lines.append("  ".join(cells))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="live terminal dashboard over a serving fleet's "
                    "/metrics (router or single replica)")
    ap.add_argument("--url", required=True,
                    help="router (fleet view) or replica base URL")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds")
    ap.add_argument("--once", action="store_true",
                    help="one frame, then exit")
    ap.add_argument("--json", action="store_true",
                    help="emit the snapshot as JSON instead of a table")
    ap.add_argument("--timeout", type=float, default=5.0,
                    help="per-poll HTTP timeout")
    args = ap.parse_args(argv)

    prev = {}
    while True:
        try:
            metrics = fetch_metrics(args.url, args.timeout)
        except (OSError, urllib.error.URLError, ValueError) as e:
            print(f"serve_top: cannot fetch {args.url}/metrics: {e}",
                  file=sys.stderr)
            if args.once:
                return 1
            time.sleep(args.interval)
            continue
        snap = build_snapshot(args.url, metrics)
        add_rates(snap, prev)
        prev = snap
        if args.json:
            print(json.dumps(snap))
        else:
            if not args.once:
                sys.stdout.write("\x1b[2J\x1b[H")     # clear + home
            print(render(snap))
        sys.stdout.flush()
        if args.once:
            return 0
        try:
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


if __name__ == "__main__":
    sys.exit(main())
