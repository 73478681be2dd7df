#!/usr/bin/env python
"""Load generator for the REST text-generation server (stdlib-only).

Drives N concurrent clients against ``PUT /api`` (or ``/api/stream``
with ``--stream``, which also measures true time-to-first-token), with
either closed-loop arrivals (each client fires its next request as soon
as the previous returns) or open-loop Poisson arrivals (``--rate``
requests/sec across the fleet — the shape real traffic has, and the one
that exposes queueing).  ``--rate_schedule "r1:t1,r2:t2,..."`` drives
piecewise rates instead (a spike→recover workload for the fleet
autoscaler), reporting per-segment throughput and p95 alongside the
run-level tables.

Reports a latency table (mean/p50/p95/p99), TTFT, token throughput, and
the server's own /metrics delta; ``--json`` emits one machine-readable
object instead (every key in ``JSON_SCHEMA_KEYS`` is always present —
asserted by tests/test_serve_bench_tool.py).

Repeat ``--url`` to spread load over a sharded front door (several
``serve_router.py`` processes over one replica fleet): each request
starts at a round-robin-chosen router and fails over to the next URL on
a transport error before the first body byte, so SIGKILLing a router
mid-run costs a retry, not a failed request.  The summary reports
per-router dispatch counts (``per_url_requests``) and how many requests
needed a sibling (``failovers``).

Repeated-prefix workloads (``--prefix_tokens N``) measure the engine's
prefix cache: a fraction of requests (``--shared_prefix_frac``) share an
N-word prompt header and differ only in a short unique tail, so cache
hits show up as ``prefill_tokens_computed`` ≪ ``prefill_tokens_
submitted`` (the ``prefill computed/submitted`` bench column).

Flag A/B (``--ab <server_flag>``, e.g. ``--ab serve_paged_kernel`` or
``--ab serve_prefill_kernel``) runs the identical workload against two
servers — one started with the named boolean flag ``on`` (``--url``)
and one with ``off`` (``--ab_url``) — and emits one result row per arm,
each tagged with ``ab_arm`` and the server's self-reported
``paged_kernel``/``prefill_kernel`` paths, so a Pallas-vs-XLA
throughput delta falls out of a single invocation.  Prefill throughput
(computed-prefill tokens/sec, from the engine's
``prefill_tokens_computed`` counter delta) is reported next to TTFT so
a prefill A/B measures the thing it changes.  ``--ab
serve_speculative`` works the same way: each arm additionally reports
the engine's drafted/accepted token deltas, the accept rate, and
accepted tokens/sec (the decode steps speculation saved).

Examples::

    python tools/serve_bench.py --port 5000 --clients 16 --requests 64
    python tools/serve_bench.py --clients 8 --rate 4 --stream --json
    python tools/serve_bench.py --clients 8 --requests 32 \\
        --prefix_tokens 256 --shared_prefix_frac 0.75 --json
    python tools/serve_bench.py --url http://host:5000 \\
        --ab serve_prefill_kernel --ab_url http://host:5001 --json
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import threading
import time
import urllib.error
import urllib.request


# keys guaranteed in the --json output (value may be None when a
# measurement is unavailable, e.g. no engine /metrics to delta)
JSON_SCHEMA_KEYS = (
    "url", "urls", "per_url_requests", "failovers",
    "clients", "requests", "ok", "errors", "status_counts",
    "wall_secs", "requests_per_sec", "tokens_total", "tokens_per_sec",
    "latency_mean_secs", "latency_p50_secs", "latency_p95_secs",
    "latency_p99_secs", "ttft_mean_secs", "ttft_p50_secs",
    "ttft_p95_secs", "tpot_mean_secs", "tpot_p50_secs",
    "tpot_p95_secs", "stream", "rate", "rate_schedule", "segments",
    "prefix_tokens",
    "shared_prefix_frac", "prefill_tokens_submitted",
    "prefill_tokens_computed", "prefill_tokens_cached",
    "prefill_computed_frac", "prefill_tokens_per_sec",
    "prefix_cache_hits", "prefix_cache_misses",
    "prefix_cache_evictions", "paged_kernel", "prefill_kernel",
    # resilience counters (engine/server /metrics deltas over the run)
    "engine_restarts", "slots_evicted_nonfinite", "preemptions",
    "drained",
    # speculative decoding (engine counter deltas; accept_rate =
    # accepted/drafted, accepted_tokens_per_sec = draft-attributed
    # "free" tokens over the run wall clock)
    "drafted_tokens", "accepted_tokens", "accept_rate",
    "accepted_tokens_per_sec",
    # engine-loop goodput over the run (loop_profiler counter deltas):
    # the share of the loop's busy time the host waited in dispatch +
    # fetch against its own work — the before/after line a
    # host/device-overlap A/B reads
    "wait_pct", "host_bubble_pct",
    # cache observatory (engine cache block deltas over the run):
    # skewed-popularity workload knobs, the miss-cause split, eviction
    # forensics, and per-ghost-tier projected hit rates ({"x2": ...})
    "prefix_zipf", "prefix_pool",
    "cache_miss_cold", "cache_miss_evicted",
    "cache_evictions_capacity", "cache_evictions_churn",
    "ghost_hit_rates",
    # hierarchical KV cache (host-RAM spill tier deltas over the run):
    # blocks rescued from host RAM, pages spilled device->host, and the
    # swap-in volume/time — the numbers a --serve_host_cache_bytes A/B
    # moves when the prefix pool exceeds the HBM budget
    "cache_host_hits", "cache_host_spills", "cache_swap_in_blocks",
    "cache_swap_in_secs",
    # client-observed SLO attainment (--slo_gate): per-request joint
    # pass/fail against the TTFT/TPOT targets — a failed request counts
    # as NOT attained; requests without a streamed TTFT/TPOT sample
    # gate on success only
    "ttft_slo_secs", "tpot_slo_secs", "slo_joint_attainment",
    "slo_gate",
)

# Exit codes: 0 = all requests succeeded; 1 = at least one request
# failed; 2 = argparse/usage error; 3 = --slo_gate given and the joint
# SLO attainment (min across arms under --ab) fell below the gate.
# CI reads these — renumbering is a breaking change.


def parse_rate_schedule(spec: str):
    """``"r1:t1,r2:t2,..."`` -> [(rate_req_per_sec, duration_secs)].
    A ``0`` rate is a silent segment (drain pause in a spike->recover
    workload)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        rate_s, sep, dur_s = part.partition(":")
        if not sep:
            raise ValueError(
                f"rate_schedule segment {part!r} is not 'rate:secs'")
        rate, dur = float(rate_s), float(dur_s)
        if rate < 0 or dur <= 0:
            raise ValueError(
                f"rate_schedule segment {part!r} needs rate >= 0 and "
                f"secs > 0")
        out.append((rate, dur))
    if not out:
        raise ValueError("empty rate_schedule")
    return out


def build_arrivals(schedule, seed: int):
    """Deterministic Poisson arrival times over the piecewise schedule:
    ``[(offset_secs, segment_idx), ...]`` sorted by time.  Pre-generated
    so every client sleeps toward an absolute deadline — the spike stays
    a spike even when slow responses bunch the clients up."""
    rng = random.Random(seed * 1000003 + 17)
    arrivals = []
    t0 = 0.0
    for i, (rate, dur) in enumerate(schedule):
        if rate > 0:
            t = t0 + rng.expovariate(rate)
            while t < t0 + dur:
                arrivals.append((t, i))
                t += rng.expovariate(rate)
        t0 += dur
    return arrivals


def _percentile(values, q: float):
    if not values:
        return None
    s = sorted(values)
    return s[min(int(q * (len(s) - 1) + 0.5), len(s) - 1)]


def _fetch_metrics(base_urls, timeout: float = 10.0):
    """First URL that answers /metrics wins (with a sharded front door
    any router speaks for the fleet)."""
    if isinstance(base_urls, str):
        base_urls = [base_urls]
    for base_url in base_urls:
        try:
            with urllib.request.urlopen(base_url + "/metrics",
                                        timeout=timeout) as resp:
                return json.loads(resp.read())
        except Exception:
            continue
    return None


def _one_request(base_urls, payload: dict, stream: bool,
                 timeout: float, start: int = 0) -> dict:
    """One request with client-side front-door failover: URLs are tried
    round-robin from ``start``, moving to the next ONLY on a transport
    error before the first body byte (status 0, nothing streamed).  An
    HTTP error means the server answered (429 brownout etc.) and a
    mid-stream death means tokens were already consumed — neither is
    retried here, so no request is ever issued twice past first byte.
    The winning URL lands in ``served_by`` and the number of siblings
    tried in ``failovers``."""
    urls = [base_urls] if isinstance(base_urls, str) else list(base_urls)
    r = {}
    for k in range(max(len(urls), 1)):
        url = urls[(start + k) % len(urls)]
        r = _one_request_to(url, payload, stream, timeout)
        r["served_by"] = url
        r["failovers"] = k
        if r["ok"] or r["status"] != 0 or r.get("mid_stream"):
            break
    return r


def _one_request_to(base_url: str, payload: dict, stream: bool,
                    timeout: float) -> dict:
    """Returns {ok, status, secs, ttft_secs, tpot_secs, tokens, error?}.
    TPOT (time per output token) is client-observed inter-token latency
    — (last token - first token) / (tokens - 1) — measurable only on the
    streaming path, where tokens arrive one SSE event at a time."""
    path = "/api/stream" if stream else "/api"
    req = urllib.request.Request(
        base_url + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="PUT")
    t0 = time.perf_counter()
    ttft = None
    t_last = None
    tokens = 0
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            if stream:
                for raw in resp:
                    line = raw.strip()
                    if not line.startswith(b"data: "):
                        continue
                    ev = json.loads(line[len(b"data: "):])
                    if "token" in ev:
                        t_last = time.perf_counter()
                        if ttft is None:
                            ttft = t_last - t0
                        tokens += 1
                    if ev.get("done"):
                        break
            else:
                body = json.loads(resp.read())
                ttft = time.perf_counter() - t0
                toks = body.get("tokens")
                if isinstance(toks, list):
                    tokens = sum(len(t) for t in toks
                                 if isinstance(t, list))
            tpot = None
            if stream and tokens > 1 and ttft is not None:
                tpot = (t_last - (t0 + ttft)) / (tokens - 1)
            return {"ok": True, "status": 200,
                    "secs": time.perf_counter() - t0,
                    "ttft_secs": ttft, "tpot_secs": tpot,
                    "tokens": tokens}
    except urllib.error.HTTPError as e:
        e.read()
        return {"ok": False, "status": e.code,
                "secs": time.perf_counter() - t0, "ttft_secs": None,
                "tpot_secs": None, "tokens": 0,
                "retry_after": e.headers.get("Retry-After")}
    except Exception as e:  # noqa: BLE001 - a bench must not die mid-run
        return {"ok": False, "status": 0,
                "secs": time.perf_counter() - t0, "ttft_secs": None,
                "tpot_secs": None, "tokens": 0,
                # tokens already streamed: failover must NOT re-issue
                "mid_stream": ttft is not None,
                "error": f"{type(e).__name__}: {e}"}


def _zipf_rank(rng, pool: int, alpha: float) -> int:
    """Draw a rank in [0, pool) with probability proportional to
    1/(rank+1)**alpha — rank 0 is the hottest prefix."""
    weights = [1.0 / (r + 1) ** alpha for r in range(max(pool, 1))]
    u = rng.random() * sum(weights)
    acc = 0.0
    for r, w in enumerate(weights):
        acc += w
        if u <= acc:
            return r
    return len(weights) - 1


def build_prompt(ticket: int, prompt: str, prefix_tokens: int,
                 shared_prefix_frac: float, seed: int,
                 prefix_zipf: float = 0.0, prefix_pool: int = 16) -> str:
    """Per-ticket prompt for the repeated-prefix workload.  A
    ``shared_prefix_frac`` fraction of tickets open with the same
    ``prefix_tokens``-word header (one small-number word ≈ one token for
    numeric tokenizers) and differ only in a short unique tail; the rest
    get fully unique prompts.  Deterministic in (ticket, seed).

    With ``prefix_zipf`` > 0 the shared header is instead drawn from a
    pool of ``prefix_pool`` distinct prefixes with Zipf(alpha)-skewed
    popularity — the workload the cache observatory's heat table and
    ghost capacity tiers are built to attribute (a few hot prefixes,
    a long cold tail that churns the LRU)."""
    if prefix_tokens <= 0:
        return prompt
    rng = random.Random(seed * 100003 + ticket)
    tail = " ".join(str(rng.randrange(10, 50)) for _ in range(4))
    if rng.random() < shared_prefix_frac:
        if prefix_zipf > 0:
            word = str(100 + _zipf_rank(rng, prefix_pool, prefix_zipf))
        else:
            word = "7"
        header = " ".join([word] * prefix_tokens)
        return f"{header} {tail}"
    # unique header of the same length: submits the same prefill volume
    # but can never hit the shared-prefix cache entries
    header = " ".join(str(rng.randrange(10, 50))
                      for _ in range(prefix_tokens))
    return f"{header} {tail}"


def run_bench(base_url: str, clients: int = 4, requests: int = 16,
              tokens: int = 32, prompt: str = "1 2 3 4",
              rate: float = 0.0, stream: bool = False,
              timeout: float = 300.0, seed: int = 0,
              prefix_tokens: int = 0,
              shared_prefix_frac: float = 1.0,
              prefix_zipf: float = 0.0,
              prefix_pool: int = 16,
              rate_schedule: str = None,
              temperature: float = None,
              ttft_slo: float = 1.0,
              tpot_slo: float = 0.25) -> dict:
    """Drive the load and aggregate results (importable — the tier-1
    smoke test calls this directly against an in-process server).

    With ``rate_schedule`` ("r1:t1,r2:t2,...") the request count and
    arrival times come from the piecewise Poisson schedule —
    ``requests`` and ``rate`` are ignored — and the summary gains a
    per-segment breakdown (``segments``).

    ``base_url`` may be a list of front-door URLs (a sharded router
    tier): requests round-robin across them and fail over to the next
    on a transport error before first byte."""
    urls = [base_url] if isinstance(base_url, str) else list(base_url)
    results = []
    results_lock = threading.Lock()
    schedule = parse_rate_schedule(rate_schedule) if rate_schedule \
        else None
    arrivals = build_arrivals(schedule, seed) if schedule else None
    n_total = len(arrivals) if arrivals is not None \
        else max(int(requests), 1)
    issued = {"n": 0}
    issue_lock = threading.Lock()
    rng = random.Random(seed)
    start_gate = threading.Event()
    t_start = None

    def take_ticket():
        with issue_lock:
            if issued["n"] >= n_total:
                return None
            issued["n"] += 1
            return issued["n"] - 1

    def client_loop():
        start_gate.wait()
        while True:
            ticket = take_ticket()
            if ticket is None:
                return
            segment = None
            if arrivals is not None:
                # absolute deadline, not a relative gap: late clients
                # don't stretch the schedule
                offset, segment = arrivals[ticket]
                delay = (t_start + offset) - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            elif rate > 0:
                # open-loop Poisson arrivals across the fleet: each
                # client sleeps an exponential gap scaled by fleet size
                time.sleep(rng.expovariate(rate / max(clients, 1)))
            payload = {"prompts": [build_prompt(
                           ticket, prompt, prefix_tokens,
                           shared_prefix_frac, seed,
                           prefix_zipf, prefix_pool)],
                       "tokens_to_generate": int(tokens),
                       "no_log": True}
            if temperature is not None:
                # 0.0 = greedy — the workload speculative decoding
                # drafts on (sampled slots never draft)
                payload["temperature"] = float(temperature)
            r = _one_request(urls, payload, stream, timeout,
                             start=ticket % len(urls))
            if segment is not None:
                r["segment"] = segment
            with results_lock:
                results.append(r)

    m0 = _fetch_metrics(urls)
    threads = [threading.Thread(target=client_loop, daemon=True)
               for _ in range(max(int(clients), 1))]
    for t in threads:
        t.start()
    t_start = time.perf_counter()
    start_gate.set()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    m1 = _fetch_metrics(urls)

    ok = [r for r in results if r["ok"]]
    lat = [r["secs"] for r in ok]
    ttft = [r["ttft_secs"] for r in ok if r["ttft_secs"] is not None]
    tpot = [r["tpot_secs"] for r in ok if r.get("tpot_secs") is not None]
    total_tokens = sum(r["tokens"] for r in ok)

    def _slo_attained(r):
        # joint SLO verdict per request: failures never attain; latency
        # dimensions only gate when the client actually measured them
        # (TTFT/TPOT need --stream)
        if not r["ok"]:
            return False
        t = r.get("ttft_secs")
        if t is not None and t > ttft_slo:
            return False
        tp = r.get("tpot_secs")
        if tp is not None and tp > tpot_slo:
            return False
        return True

    slo_attained = sum(1 for r in results if _slo_attained(r))
    by_status = {}
    for r in results:
        by_status[str(r["status"])] = by_status.get(str(r["status"]), 0) + 1
    per_url = {u: 0 for u in urls}
    for r in results:
        served = r.get("served_by")
        if served in per_url:
            per_url[served] += 1
    out = {
        "url": urls[0],
        # sharded front door: every URL tried, per-router dispatch
        # counts, and how many requests needed a sibling router
        "urls": urls,
        "per_url_requests": per_url,
        "failovers": sum(r.get("failovers", 0) for r in results),
        "clients": clients,
        "requests": len(results),
        "ok": len(ok),
        "errors": len(results) - len(ok),
        "status_counts": by_status,
        "wall_secs": wall,
        "requests_per_sec": len(ok) / wall if wall > 0 else None,
        "tokens_total": total_tokens,
        "tokens_per_sec": total_tokens / wall if wall > 0 else None,
        "latency_mean_secs": sum(lat) / len(lat) if lat else None,
        "latency_p50_secs": _percentile(lat, 0.50),
        "latency_p95_secs": _percentile(lat, 0.95),
        "latency_p99_secs": _percentile(lat, 0.99),
        "ttft_mean_secs": sum(ttft) / len(ttft) if ttft else None,
        "ttft_p50_secs": _percentile(ttft, 0.50),
        "ttft_p95_secs": _percentile(ttft, 0.95),
        # client-observed per-output-token decode latency (--stream only)
        "tpot_mean_secs": sum(tpot) / len(tpot) if tpot else None,
        "tpot_p50_secs": _percentile(tpot, 0.50),
        "tpot_p95_secs": _percentile(tpot, 0.95),
        "stream": stream,
        "rate": rate,
        # piecewise-rate workload (--rate_schedule): the spec string and
        # a per-segment breakdown (filled below), None on constant rate
        "rate_schedule": rate_schedule,
        "segments": None,
        "prefix_tokens": prefix_tokens,
        "shared_prefix_frac": shared_prefix_frac,
        "prefix_zipf": prefix_zipf,
        "prefix_pool": prefix_pool,
        # prefix-cache effectiveness (engine /metrics deltas; None when
        # the server has no engine metrics to delta)
        "prefill_tokens_submitted": None,
        "prefill_tokens_computed": None,
        "prefill_tokens_cached": None,
        "prefill_computed_frac": None,
        # computed-prefill tokens/sec over the run wall clock — the
        # number a prefill-kernel A/B actually changes
        "prefill_tokens_per_sec": None,
        "prefix_cache_hits": None,
        "prefix_cache_misses": None,
        "prefix_cache_evictions": None,
        # which attention paths served the run ('pallas'|'xla', from the
        # engine /metrics block) — makes bench rows attributable
        "paged_kernel": None,
        "prefill_kernel": None,
        # resilience activity during the run (engine restarts, sentinel
        # slot evictions, pool-pressure preemptions, drain initiations)
        "engine_restarts": None,
        "slots_evicted_nonfinite": None,
        "preemptions": None,
        "drained": None,
        # speculative decoding: drafted/accepted engine counter deltas,
        # their ratio, and accepted tokens/sec — the number a
        # --ab serve_speculative run actually changes
        "drafted_tokens": None,
        "accepted_tokens": None,
        "accept_rate": None,
        "accepted_tokens_per_sec": None,
        # engine-loop goodput (loop_profiler deltas over the run)
        "wait_pct": None,
        "host_bubble_pct": None,
        # cache observatory (engine cache block deltas over the run):
        # miss-cause split, eviction forensics, and per-ghost-tier
        # projected hit rates computed from hit/probe counter deltas
        "cache_miss_cold": None,
        "cache_miss_evicted": None,
        "cache_evictions_capacity": None,
        "cache_evictions_churn": None,
        "ghost_hit_rates": None,
        # hierarchical KV cache (host-RAM spill tier counter deltas)
        "cache_host_hits": None,
        "cache_host_spills": None,
        "cache_swap_in_blocks": None,
        "cache_swap_in_secs": None,
        # client-observed joint SLO attainment against the targets
        # above; "slo_gate" echoes --slo_gate (None when no gate)
        "ttft_slo_secs": ttft_slo,
        "tpot_slo_secs": tpot_slo,
        "slo_joint_attainment": (round(slo_attained / len(results), 4)
                                 if results else None),
        "slo_gate": None,
    }
    if schedule:
        segs = []
        for i, (seg_rate, seg_dur) in enumerate(schedule):
            rs = [r for r in results if r.get("segment") == i]
            oks = [r for r in rs if r["ok"]]
            seg_lat = [r["secs"] for r in oks]
            seg_ttft = [r["ttft_secs"] for r in oks
                        if r["ttft_secs"] is not None]
            segs.append({
                "segment": i,
                "rate": seg_rate,
                "duration_secs": seg_dur,
                "requests": len(rs),
                "ok": len(oks),
                "errors": len(rs) - len(oks),
                "requests_per_sec": round(len(oks) / seg_dur, 3),
                "latency_p95_secs": _percentile(seg_lat, 0.95),
                "ttft_p95_secs": _percentile(seg_ttft, 0.95),
            })
        out["segments"] = segs
    if m0 is not None and m1 is not None:
        # a router /metrics nests the fleet-summed engine counters (and
        # request counts) under "aggregate" — delta those transparently
        if "aggregate" in m1 and "engine" not in m1:
            m0 = m0.get("aggregate") or {}
            m1 = m1.get("aggregate") or {}
        out["server_metrics_delta"] = {
            "requests": m1.get("requests", 0) - m0.get("requests", 0),
            "errors": m1.get("errors", 0) - m0.get("errors", 0),
            "throttled": m1.get("throttled", 0) - m0.get("throttled", 0),
        }
        if isinstance(m0.get("drained"), (int, float)) \
                and isinstance(m1.get("drained"), (int, float)):
            out["drained"] = m1["drained"] - m0["drained"]
        e0, e1 = m0.get("engine"), m1.get("engine")
        if isinstance(e1, dict):
            out["server_engine"] = e1
            out["paged_kernel"] = e1.get("paged_kernel")
            out["prefill_kernel"] = e1.get("prefill_kernel")
            if isinstance(e0, dict):
                def delta(key):
                    a, b = e0.get(key), e1.get(key)
                    if isinstance(a, (int, float)) \
                            and isinstance(b, (int, float)):
                        return b - a
                    return None
                for key in ("prefill_tokens_submitted",
                            "prefill_tokens_computed",
                            "prefill_tokens_cached",
                            "prefix_cache_hits", "prefix_cache_misses",
                            "prefix_cache_evictions",
                            "engine_restarts",
                            "slots_evicted_nonfinite",
                            "preemptions",
                            "drafted_tokens", "accepted_tokens"):
                    out[key] = delta(key)
                sub, comp = (out["prefill_tokens_submitted"],
                             out["prefill_tokens_computed"])
                if sub and comp is not None:
                    out["prefill_computed_frac"] = round(comp / sub, 4)
                if comp is not None and wall > 0:
                    out["prefill_tokens_per_sec"] = round(comp / wall, 3)
                drafted, accepted = (out["drafted_tokens"],
                                     out["accepted_tokens"])
                if drafted and accepted is not None:
                    out["accept_rate"] = round(accepted / drafted, 4)
                if accepted is not None and wall > 0:
                    out["accepted_tokens_per_sec"] = round(
                        accepted / wall, 3)
                # engine-loop goodput: recompute the busy-time split
                # from cumulative loop counter deltas (the percentages
                # themselves never delta or sum; a router's aggregate
                # sums the per-replica counters, which still deltas
                # correctly)
                # cache observatory: miss-cause / forensics deltas and
                # ghost tier hit rates over this run's probes only
                c0 = e0.get("cache")
                c1 = e1.get("cache")
                if isinstance(c0, dict) and isinstance(c1, dict):
                    def cache_delta(key):
                        a, b = c0.get(key), c1.get(key)
                        if isinstance(a, (int, float)) \
                                and isinstance(b, (int, float)):
                            return b - a
                        return None
                    out["cache_miss_cold"] = cache_delta("miss_cold")
                    out["cache_miss_evicted"] = cache_delta("miss_evicted")
                    out["cache_evictions_capacity"] = cache_delta(
                        "evictions_capacity")
                    out["cache_evictions_churn"] = cache_delta(
                        "evictions_churn")
                    g0 = c0.get("ghost")
                    g1 = c1.get("ghost")
                    if isinstance(g0, dict) and isinstance(g1, dict):
                        rates = {}
                        for tier, t1 in sorted(g1.items()):
                            t0g = g0.get(tier)
                            if not (isinstance(t0g, dict)
                                    and isinstance(t1, dict)):
                                continue
                            dh = (t1.get("hits") or 0) - \
                                (t0g.get("hits") or 0)
                            dp = dh + (t1.get("misses") or 0) - \
                                (t0g.get("misses") or 0)
                            if dp > 0:
                                rates[tier] = round(dh / dp, 4)
                        if rates:
                            out["ghost_hit_rates"] = rates
                    # host-RAM spill tier: two-tier hit attribution
                    # lives on the observatory (host_hits,
                    # swap_in_blocks); spill/swap-in volume on the
                    # tier's own sub-block (cache.host.*)
                    out["cache_host_hits"] = cache_delta("host_hits")
                    out["cache_swap_in_blocks"] = cache_delta(
                        "swap_in_blocks")
                    h0 = c0.get("host")
                    h1 = c1.get("host")
                    if isinstance(h0, dict) and isinstance(h1, dict):
                        def host_delta(key):
                            a, b = h0.get(key), h1.get(key)
                            if isinstance(a, (int, float)) \
                                    and isinstance(b, (int, float)):
                                return b - a
                            return None
                        out["cache_host_spills"] = host_delta(
                            "spills_completed")
                        sw = host_delta("swap_in_secs")
                        if sw is not None:
                            out["cache_swap_in_secs"] = round(sw, 6)
                l0 = e0.get("loop")
                l1 = e1.get("loop")
                if isinstance(l0, dict) and isinstance(l1, dict):
                    def loop_delta(key):
                        a, b = l0.get(key), l1.get(key)
                        if isinstance(a, (int, float)) \
                                and isinstance(b, (int, float)):
                            return b - a
                        return None
                    wait = loop_delta("wait_secs")
                    busy = loop_delta("wall_secs")
                    gap = loop_delta("gap_secs")
                    if wait is not None and busy is not None:
                        busy += gap or 0.0
                        if busy > 0:
                            pct = 100.0 * min(wait / busy, 1.0)
                            out["wait_pct"] = round(pct, 3)
                            out["host_bubble_pct"] = round(100.0 - pct, 3)
    return out


def run_ab(urls, labels, **kw) -> list:
    """Kernel A/B: run the identical workload once per arm (a server
    started with ``--serve_paged_kernel on`` and one with ``off``) and
    tag each row with its arm label plus the attention path the server
    actually reports — both rows land in the ``--json`` output."""
    rows = []
    for label, url in zip(labels, urls):
        r = run_bench(url, **kw)
        r["ab_arm"] = label
        rows.append(r)
    return rows


def _fmt(v, unit=""):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.3f}{unit}"
    return f"{v}{unit}"


def print_table(r: dict) -> None:
    rows = [
        ("requests (ok/total)", f"{r['ok']}/{r['requests']}"),
        ("status counts", json.dumps(r["status_counts"])),
        ("wall time", _fmt(r["wall_secs"], "s")),
        ("throughput", _fmt(r["requests_per_sec"], " req/s")),
        ("token throughput", _fmt(r["tokens_per_sec"], " tok/s")),
        ("latency mean", _fmt(r["latency_mean_secs"], "s")),
        ("latency p50", _fmt(r["latency_p50_secs"], "s")),
        ("latency p95", _fmt(r["latency_p95_secs"], "s")),
        ("latency p99", _fmt(r["latency_p99_secs"], "s")),
        ("ttft mean", _fmt(r["ttft_mean_secs"], "s")),
        ("ttft p50", _fmt(r["ttft_p50_secs"], "s")),
        ("ttft p95", _fmt(r["ttft_p95_secs"], "s")),
        ("tpot p50", _fmt(r["tpot_p50_secs"], "s")),
        ("tpot p95", _fmt(r["tpot_p95_secs"], "s")),
    ]
    if len(r.get("urls") or ()) > 1:
        rows[1:1] = [
            ("router dispatch", json.dumps(r["per_url_requests"])),
            ("router failovers", _fmt(r["failovers"])),
        ]
    eng = r.get("server_engine")
    if eng:
        rows += [
            ("engine occupancy", _fmt(eng.get("mean_batch_occupancy"))),
            ("engine decode steps", _fmt(eng.get("decode_steps"))),
            ("engine prefill chunks", _fmt(eng.get("prefill_chunks"))),
            ("engine paged kernel", _fmt(r.get("paged_kernel"))),
            ("engine prefill kernel", _fmt(r.get("prefill_kernel"))),
        ]
    if r.get("prefill_tokens_per_sec") is not None:
        rows += [("prefill throughput",
                  _fmt(r["prefill_tokens_per_sec"], " tok/s"))]
    if r.get("wait_pct") is not None:
        rows += [("loop dispatch+fetch wait / host bubble",
                  f"{_fmt(r['wait_pct'], '%')} / "
                  f"{_fmt(r['host_bubble_pct'], '%')}")]
    if r.get("drafted_tokens") is not None:
        rows += [
            ("spec accepted/drafted",
             f"{_fmt(r['accepted_tokens'])}/{_fmt(r['drafted_tokens'])}"
             + (f" ({_fmt(r['accept_rate'])})"
                if r.get("accept_rate") is not None else "")),
        ]
        if r.get("accepted_tokens_per_sec") is not None:
            rows += [("spec accepted throughput",
                      _fmt(r["accepted_tokens_per_sec"], " tok/s"))]
    if r.get("prefill_tokens_submitted") is not None:
        rows += [
            ("prefill computed/submitted",
             f"{_fmt(r['prefill_tokens_computed'])}/"
             f"{_fmt(r['prefill_tokens_submitted'])}"
             + (f" ({_fmt(r['prefill_computed_frac'])})"
                if r.get("prefill_computed_frac") is not None else "")),
            ("prefix cache hit/miss/evict",
             f"{_fmt(r['prefix_cache_hits'])}/"
             f"{_fmt(r['prefix_cache_misses'])}/"
             f"{_fmt(r['prefix_cache_evictions'])}"),
        ]
    if r.get("cache_miss_cold") is not None:
        rows += [
            ("cache miss cold/evicted",
             f"{_fmt(r['cache_miss_cold'])}/"
             f"{_fmt(r['cache_miss_evicted'])}"),
            ("cache evict capacity/churn",
             f"{_fmt(r['cache_evictions_capacity'])}/"
             f"{_fmt(r['cache_evictions_churn'])}"),
        ]
    if r.get("ghost_hit_rates"):
        rows += [("ghost tier hit rates",
                  " ".join(f"{t}={v:.3f}"
                           for t, v in sorted(r["ghost_hit_rates"].items())))]
    if r.get("cache_host_hits") is not None:
        rows += [("host tier hit/spill/swap-in",
                  f"{_fmt(r['cache_host_hits'])}/"
                  f"{_fmt(r['cache_host_spills'])}/"
                  f"{_fmt(r['cache_swap_in_blocks'])}"
                  + (f" ({_fmt(r['cache_swap_in_secs'], 's')} swap)"
                     if r.get("cache_swap_in_secs") is not None else ""))]
    w = max(len(k) for k, _ in rows)
    print(f"serve_bench: {r['clients']} clients -> {r['url']}"
          + (" (stream)" if r["stream"] else ""))
    for k, v in rows:
        print(f"  {k:<{w}}  {v}")
    if r.get("segments"):
        print(f"  rate schedule ({r.get('rate_schedule')}):")
        print(f"    {'seg':>3} {'rate':>8} {'secs':>7} {'ok/total':>9} "
              f"{'req/s':>8} {'lat p95':>9} {'ttft p95':>9}")
        for s in r["segments"]:
            print(f"    {s['segment']:>3} {_fmt(s['rate']):>8} "
                  f"{_fmt(s['duration_secs']):>7} "
                  f"{s['ok']}/{s['requests']:<7} "
                  f"{_fmt(s['requests_per_sec']):>8} "
                  f"{_fmt(s['latency_p95_secs']):>9} "
                  f"{_fmt(s['ttft_p95_secs']):>9}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=5000)
    p.add_argument("--url", default=None, action="append",
                   help="full base URL (overrides --host/--port); "
                        "repeat for a sharded front door — requests "
                        "round-robin over the URLs and fail over to the "
                        "next on a transport error before first byte")
    p.add_argument("--clients", type=int, default=4)
    p.add_argument("--requests", type=int, default=16,
                   help="total requests across all clients")
    p.add_argument("--tokens", type=int, default=32,
                   help="tokens_to_generate per request")
    p.add_argument("--prompt", default="1 2 3 4")
    p.add_argument("--rate", type=float, default=0.0,
                   help="open-loop Poisson arrival rate in req/s across "
                        "the fleet (0 = closed loop)")
    p.add_argument("--rate_schedule", default=None,
                   metavar="R1:T1,R2:T2,...",
                   help="piecewise open-loop Poisson rates (req/s for "
                        "secs each; 0 rate = silent pause) for "
                        "spike->recover workloads; overrides --rate and "
                        "--requests and adds a per-segment table")
    p.add_argument("--stream", action="store_true",
                   help="use /api/stream (measures true TTFT)")
    p.add_argument("--timeout", type=float, default=300.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--temperature", type=float, default=None,
                   help="per-request sampling temperature (0 = greedy, "
                        "the mode speculative decoding drafts on); "
                        "omitted from the payload by default")
    p.add_argument("--prefix_tokens", type=int, default=0,
                   help="repeated-prefix workload: shared prompt header "
                        "length in words (0 = off, all prompts identical "
                        "to --prompt)")
    p.add_argument("--prefix_zipf", type=float, default=0.0,
                   help="skewed-popularity prefix workload: draw each "
                        "shared header from a pool of --prefix_pool "
                        "distinct prefixes with Zipf(ALPHA) popularity "
                        "(0 = single shared prefix, the default)")
    p.add_argument("--prefix_pool", type=int, default=16,
                   help="distinct shared prefixes for --prefix_zipf")
    p.add_argument("--shared_prefix_frac", type=float, default=1.0,
                   help="fraction of requests sharing the header; the "
                        "rest get unique same-length headers")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit one JSON object instead of the table")
    p.add_argument("--slo_gate", type=float, default=None,
                   metavar="FRAC",
                   help="exit 3 unless the joint SLO attainment "
                        "(fraction of requests succeeding within "
                        "--ttft_slo and --tpot_slo; failures never "
                        "attain) reaches FRAC; under --ab the MIN "
                        "across both arms gates")
    p.add_argument("--ttft_slo", type=float, default=1.0,
                   help="time-to-first-token target in seconds for "
                        "--slo_gate (matches serve_report's default)")
    p.add_argument("--tpot_slo", type=float, default=0.25,
                   help="per-output-token target in seconds for "
                        "--slo_gate (matches serve_report's default)")
    p.add_argument("--ab", default=None, metavar="SERVER_FLAG",
                   help="A/B comparison over any boolean server flag "
                        "(e.g. serve_paged_kernel, serve_prefill_kernel): "
                        "run the workload against --url (the flag-ON "
                        "server) and --ab_url (the flag-OFF server), "
                        "emitting one row per arm")
    p.add_argument("--ab_url", default=None,
                   help="base URL of the second (flag-OFF) server for "
                        "--ab")
    args = p.parse_args(argv)
    base_url = args.url or [f"http://{args.host}:{args.port}"]
    kw = dict(clients=args.clients, requests=args.requests,
              tokens=args.tokens, prompt=args.prompt, rate=args.rate,
              stream=args.stream, timeout=args.timeout, seed=args.seed,
              prefix_tokens=args.prefix_tokens,
              shared_prefix_frac=args.shared_prefix_frac,
              prefix_zipf=args.prefix_zipf,
              prefix_pool=args.prefix_pool,
              rate_schedule=args.rate_schedule,
              temperature=args.temperature,
              ttft_slo=args.ttft_slo, tpot_slo=args.tpot_slo)

    def slo_gate_rc(rows):
        # exit 3 on gate miss — distinct from 1 (request errors) so a
        # sweep can tell "server broke" from "server too slow"
        if args.slo_gate is None:
            return None
        atts = [r.get("slo_joint_attainment") for r in rows]
        worst = min((a for a in atts if a is not None), default=None)
        if worst is None or worst < args.slo_gate:
            print(f"SLO gate FAILED: joint attainment "
                  f"{worst if worst is not None else 'unmeasured'} "
                  f"< {args.slo_gate}", file=sys.stderr)
            return 3
        return None

    if args.ab:
        if not args.ab_url:
            p.error("--ab needs --ab_url (the second arm's server)")
        rows = run_ab([base_url, args.ab_url], ["on", "off"], **kw)
        for row in rows:
            row["slo_gate"] = args.slo_gate
        if args.as_json:
            print(json.dumps({"ab": args.ab, "rows": rows}, indent=2))
        else:
            for r in rows:
                served = (f"decode={r.get('paged_kernel') or 'unknown'} "
                          f"prefill={r.get('prefill_kernel') or 'unknown'}")
                print(f"--- {args.ab}={r['ab_arm']} (served by: {served})")
                print_table(r)
            on, off = rows
            if on["tokens_per_sec"] and off["tokens_per_sec"]:
                print(f"A/B token throughput on/off: "
                      f"{on['tokens_per_sec']:.3f} / "
                      f"{off['tokens_per_sec']:.3f} tok/s "
                      f"({on['tokens_per_sec'] / off['tokens_per_sec']:.2f}x)")
            if on.get("accept_rate") is not None or \
                    off.get("accept_rate") is not None:
                print(f"A/B spec accept rate on/off: "
                      f"{_fmt(on.get('accept_rate'))} / "
                      f"{_fmt(off.get('accept_rate'))} "
                      f"(accepted {_fmt(on.get('accepted_tokens'))} / "
                      f"{_fmt(off.get('accepted_tokens'))} tok)")
            if on.get("prefill_tokens_per_sec") and \
                    off.get("prefill_tokens_per_sec"):
                print(f"A/B prefill throughput on/off: "
                      f"{on['prefill_tokens_per_sec']:.3f} / "
                      f"{off['prefill_tokens_per_sec']:.3f} tok/s "
                      f"({on['prefill_tokens_per_sec'] / off['prefill_tokens_per_sec']:.2f}x)")
            if on.get("wait_pct") is not None or \
                    off.get("wait_pct") is not None:
                # the loop-overlap A/B readout: did the flag move the
                # host bubble, and did tokens/sec follow?
                print(f"A/B loop dispatch+fetch wait on/off: "
                      f"{_fmt(on.get('wait_pct'), '%')} / "
                      f"{_fmt(off.get('wait_pct'), '%')} "
                      f"(host bubble "
                      f"{_fmt(on.get('host_bubble_pct'), '%')} / "
                      f"{_fmt(off.get('host_bubble_pct'), '%')})")
            if on.get("cache_host_hits") or off.get("cache_host_hits"):
                # the hierarchical-cache A/B readout: blocks rescued
                # from host RAM, and did mean TTFT follow?
                print(f"A/B host-tier hit blocks on/off: "
                      f"{_fmt(on.get('cache_host_hits'))} / "
                      f"{_fmt(off.get('cache_host_hits'))} "
                      f"(ttft mean "
                      f"{_fmt(on.get('ttft_mean_secs'), 's')} / "
                      f"{_fmt(off.get('ttft_mean_secs'), 's')})")
        rc = slo_gate_rc(rows)
        if rc is not None:
            return rc
        return 0 if all(r["errors"] == 0 for r in rows) else 1
    r = run_bench(base_url, **kw)
    r["slo_gate"] = args.slo_gate
    if args.as_json:
        print(json.dumps(r, indent=2))
    else:
        print_table(r)
    rc = slo_gate_rc([r])
    if rc is not None:
        return rc
    return 0 if r["errors"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
