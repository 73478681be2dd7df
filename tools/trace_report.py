#!/usr/bin/env python
"""Summarize a run's span trace (trace.json) + telemetry stream.

Reads the Chrome ``trace_event`` JSON written by ``--trace_dir``
(megatron_llm_tpu/tracing.py) — the same file Perfetto loads — and
prints:

* a goodput breakdown — wall-clock attributed to productive-step /
  compile / checkpoint / eval / rewind / data-stall / other, with
  ``goodput_pct`` and a bar chart
* span coverage — how much of the traced wall-clock any span accounts
  for (the acceptance bar is >= 95%)
* the top-N slowest spans (the root ``train`` span excluded — it always
  "wins")
* a recompile timeline — every steady-state backend compile, timestamped
* a straggler timeline — per-host straggler events (which host, which
  section, how far past the median)

When the sibling ``telemetry.jsonl`` (``--structured_log_dir``) exists,
the per-boundary ``goodput_pct`` trend is appended.

``--merge`` stitches several processes' traces (e.g. the serving
router's + each replica's) onto ONE Chrome-trace timeline: every
trace carries its wall-clock origin (``otherData.trace_start_unix``),
so events shift onto a shared clock and each input file becomes its own
process row.  A request's ``route_request`` span (router) then lines up
under the same trace id as its ``queue_wait`` / ``prefill_chunk`` /
``decode_step`` spans (replica) — the fleet-wide request lifecycle in
one Perfetto view.

Pure stdlib — no jax import, runs anywhere the files do.

Usage:
    python tools/trace_report.py TRACE_DIR_OR_JSON [--top N] [--json]
    python tools/trace_report.py A/trace.json B/trace.json --merge \
        --out merged.json [--trace TRACE_ID]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

GOODPUT_ORDER = ("step", "compile", "checkpoint", "eval", "rewind", "data",
                 "other")
BAR_WIDTH = 40


def load_trace(path: str) -> Dict:
    """Accept a trace.json file or the --trace_dir holding one."""
    if os.path.isdir(path):
        path = os.path.join(path, "trace.json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no trace at {path}")
    with open(path) as f:
        return json.load(f)


def spans(trace: Dict) -> List[Dict]:
    """The complete ('X') events, sorted by start time."""
    evs = [e for e in trace.get("traceEvents", []) if e.get("ph") == "X"]
    return sorted(evs, key=lambda e: e.get("ts", 0.0))


def instants(trace: Dict, name: Optional[str] = None) -> List[Dict]:
    return [e for e in trace.get("traceEvents", [])
            if e.get("ph") == "i" and (name is None or e.get("name") == name)]


def coverage(trace: Dict) -> Optional[float]:
    """Fraction of the traced wall-clock covered by at least one span:
    union of [ts, ts+dur) intervals over the trace's own extent.  None
    when the trace holds no spans."""
    xs = spans(trace)
    if not xs:
        return None
    intervals = sorted((e["ts"], e["ts"] + e.get("dur", 0.0)) for e in xs)
    lo = intervals[0][0]
    hi = max(end for _, end in intervals)
    if hi <= lo:
        return None
    covered, cur_start, cur_end = 0.0, intervals[0][0], intervals[0][1]
    for start, end in intervals[1:]:
        if start > cur_end:
            covered += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    covered += cur_end - cur_start
    return covered / (hi - lo)


def goodput_breakdown(trace: Dict) -> Optional[Dict]:
    return (trace.get("otherData") or {}).get("goodput")


def top_spans(trace: Dict, n: int = 10) -> List[Dict]:
    """Slowest spans by duration; the root 'train' span excluded."""
    xs = [e for e in spans(trace) if e.get("name") != "train"]
    xs.sort(key=lambda e: e.get("dur", 0.0), reverse=True)
    return [{"name": e["name"], "category": e.get("cat", "?"),
             "start_secs": e["ts"] / 1e6, "dur_secs": e.get("dur", 0.0) / 1e6,
             "args": {k: v for k, v in (e.get("args") or {}).items()
                      if k != "goodput"}}
            for e in xs[:n]]


def recompile_timeline(trace: Dict) -> List[Dict]:
    out = []
    for e in spans(trace):
        if e.get("name") == "recompile":
            out.append({"at_secs": e["ts"] / 1e6,
                        "compile_secs": e.get("dur", 0.0) / 1e6,
                        "program": (e.get("args") or {}).get("program")})
    return sorted(out, key=lambda r: r["at_secs"])


def startup_spans(trace: Dict) -> List[Dict]:
    """The start-up timeline (category ``startup``: tracing.startup_span)
    by start, each with how deep it lies inside the others."""
    xs = sorted((e for e in spans(trace) if e.get("cat") == "startup"),
                key=lambda e: (e["ts"], -e.get("dur", 0.0)))
    out = []
    for e in xs:
        end = e["ts"] + e.get("dur", 0.0)
        depth = sum(1 for o in xs if o is not e and o["ts"] <= e["ts"]
                    and end <= o["ts"] + o.get("dur", 0.0)
                    and o.get("dur", 0.0) > e.get("dur", 0.0))
        out.append({"name": e["name"], "at_secs": e["ts"] / 1e6,
                    "secs": e.get("dur", 0.0) / 1e6, "depth": depth})
    return out


def straggler_timeline(trace: Dict) -> List[Dict]:
    out = []
    for e in instants(trace, "straggler"):
        a = e.get("args") or {}
        out.append({"at_secs": e["ts"] / 1e6,
                    "iteration": a.get("iteration"),
                    "host": a.get("host"), "section": a.get("section"),
                    # multi-slice runs (telemetry schema 4) name the slice
                    # the straggling host belongs to; absent otherwise
                    "slice": a.get("slice"),
                    "secs": a.get("secs"), "median_secs": a.get("median_secs"),
                    "ratio": a.get("ratio")})
    return sorted(out, key=lambda r: r["at_secs"])


def goodput_trend(log_dir: str) -> List[Dict]:
    """Per-boundary goodput_pct from a sibling telemetry.jsonl (empty
    when the stream is absent or predates tracing)."""
    path = os.path.join(log_dir, "telemetry.jsonl") \
        if os.path.isdir(log_dir) else log_dir
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("kind") == "log" and rec.get("goodput_pct") is not None:
                out.append({"iteration": rec.get("iteration"),
                            "goodput_pct": rec["goodput_pct"]})
    return out


def merge_traces(traces: List[Dict],
                 names: Optional[List[str]] = None) -> Dict:
    """Merge N Chrome traces onto one timeline.

    Each SpanTracer trace's timestamps are relative to its own process
    start; ``otherData.trace_start_unix`` anchors that origin to the
    wall clock.  The earliest origin becomes the merged zero, every
    other file's events shift right by its offset, and each file gets a
    distinct pid (with a ``process_name`` metadata row naming it) so
    Perfetto shows one row per process."""
    if not traces:
        raise ValueError("nothing to merge")
    names = names or [f"trace_{i}" for i in range(len(traces))]
    origins = []
    for t in traces:
        o = (t.get("otherData") or {}).get("trace_start_unix")
        origins.append(float(o) if o is not None else None)
    known = [o for o in origins if o is not None]
    base = min(known) if known else 0.0
    events: List[Dict] = []
    for i, (t, name) in enumerate(zip(traces, names)):
        shift_us = ((origins[i] - base) * 1e6
                    if origins[i] is not None else 0.0)
        label = f"p{i}:{os.path.basename(name) or name}"
        events.append({"ph": "M", "name": "process_name", "pid": i,
                       "tid": 0, "args": {"name": label}})
        for e in t.get("traceEvents", []):
            e = dict(e)
            e["pid"] = i
            if e.get("ph") == "M":
                if e.get("name") == "process_name":
                    continue    # replaced by the per-file label above
            else:
                e["ts"] = e.get("ts", 0.0) + shift_us
            events.append(e)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "merged_from": list(names),
            "trace_start_unix": base,
        },
    }


def request_timeline(merged: Dict, trace_id: str) -> List[Dict]:
    """All events carrying a given request trace id, across every merged
    process, in time order — the 'where did this slow request spend its
    time' answer."""
    out = []
    for e in merged.get("traceEvents", []):
        if e.get("ph") == "M":
            continue
        a = e.get("args") or {}
        ids = a.get("traces") if isinstance(a.get("traces"), list) \
            else [a.get("trace")]
        if trace_id not in ids:
            continue
        out.append({"pid": e.get("pid"), "name": e.get("name"),
                    "ph": e.get("ph"), "at_secs": e.get("ts", 0.0) / 1e6,
                    "dur_secs": e.get("dur", 0.0) / 1e6,
                    "args": {k: v for k, v in a.items()
                             if k not in ("trace", "traces", "goodput")}})
    return sorted(out, key=lambda r: r["at_secs"])


def render_timeline(rows: List[Dict], trace_id: str) -> str:
    lines = [f"request {trace_id}: {len(rows)} events"]
    for r in rows:
        extra = (" " + json.dumps(r["args"], sort_keys=True)
                 if r["args"] else "")
        dur = (f" {r['dur_secs'] * 1000:.1f} ms"
               if r["ph"] == "X" else "")
        lines.append(f"  @ {r['at_secs']:9.4f}s p{r['pid']} "
                     f"{r['name']}{dur}{extra}")
    return "\n".join(lines)


def _bar(frac: float) -> str:
    n = int(round(max(min(frac, 1.0), 0.0) * BAR_WIDTH))
    return "#" * n + "." * (BAR_WIDTH - n)


def render(trace: Dict, top_n: int, trend: List[Dict]) -> str:
    lines = []
    g = goodput_breakdown(trace)
    other = trace.get("otherData") or {}
    if g:
        wall = g.get("wall_secs") or 0.0
        lines.append(f"goodput breakdown (wall {wall:.2f}s, goodput "
                     f"{g.get('goodput_pct', 0.0):.1f}%):")
        for cat in GOODPUT_ORDER:
            secs = g.get(f"{cat}_secs", 0.0)
            frac = secs / wall if wall else 0.0
            lines.append(f"  {cat:>10} {secs:9.2f}s {frac * 100:5.1f}% "
                         f"|{_bar(frac)}|")
    else:
        lines.append("(no goodput breakdown in trace)")
    cov = coverage(trace)
    if cov is not None:
        lines.append(f"\nspan coverage of traced wall-clock: "
                     f"{cov * 100:.1f}%")
    dropped = other.get("dropped_events", 0)
    if dropped:
        lines.append(f"dropped events (ring eviction): {dropped} — oldest "
                     f"history is gone; raise --trace_buffer_size")

    tops = top_spans(trace, top_n)
    if tops:
        lines.append(f"\ntop {len(tops)} slowest spans:")
        for s in tops:
            extra = (" " + json.dumps(s["args"], sort_keys=True)
                     if s["args"] else "")
            lines.append(f"  {s['dur_secs'] * 1000:10.1f} ms  "
                         f"{s['name']} [{s['category']}] "
                         f"@ {s['start_secs']:.2f}s{extra}")

    boot = startup_spans(trace)
    if boot:
        lines.append("\nstart-up timeline (first stamp to ready):")
        for b in boot:
            lines.append(f"  @ {b['at_secs']:8.2f}s {b['secs']:8.2f}s  "
                         f"{'  ' * b['depth']}{b['name']}")

    rec = recompile_timeline(trace)
    lines.append(f"\nrecompiles: {other.get('recompiles', len(rec))}")
    for r in rec:
        lines.append(f"  @ {r['at_secs']:.2f}s backend compile of "
                     f"{r.get('program') or '?'} "
                     f"{r['compile_secs']:.2f}s after steady state")

    st = straggler_timeline(trace)
    lines.append(f"\nstraggler events: {other.get('straggler_events', len(st))}")
    for s in st:
        who = (f"slice {s['slice']} host {s['host']}"
               if s.get("slice") is not None else f"host {s['host']}")
        lines.append(f"  iteration {s['iteration']}: {who} "
                     f"{s['section']} {(s['secs'] or 0.0) * 1000:.1f} ms = "
                     f"{(s['ratio'] or 0.0):.2f}x median "
                     f"({(s['median_secs'] or 0.0) * 1000:.1f} ms)")

    if trend:
        lines.append("\ngoodput_pct per log boundary:")
        for t in trend:
            lines.append(f"  iteration {t['iteration']:>8}: "
                         f"{t['goodput_pct']:5.1f}% "
                         f"|{_bar(t['goodput_pct'] / 100.0)}|")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarize a span trace (trace.json), or --merge "
                    "several processes' traces onto one timeline")
    ap.add_argument("paths", nargs="+", metavar="path",
                    help="trace.json or the --trace_dir (several with "
                         "--merge)")
    ap.add_argument("--merge", action="store_true",
                    help="merge the given traces (router + replicas) "
                         "onto one Chrome-trace timeline via their "
                         "trace_start_unix anchors")
    ap.add_argument("--out", default=None,
                    help="with --merge: write the merged Chrome trace "
                         "here (loadable in Perfetto)")
    ap.add_argument("--trace", default=None,
                    help="with --merge: print the cross-process timeline "
                         "of this request trace id")
    ap.add_argument("--log_dir", default=None,
                    help="telemetry.jsonl (or its dir) for the per-boundary "
                         "goodput trend; defaults to the trace's own dir")
    ap.add_argument("--top", type=int, default=10,
                    help="how many slowest spans to list")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as one JSON object")
    args = ap.parse_args(argv)

    if len(args.paths) > 1 and not args.merge:
        print("multiple traces require --merge", file=sys.stderr)
        return 2

    if args.merge:
        try:
            traces = [load_trace(p) for p in args.paths]
        except (FileNotFoundError, json.JSONDecodeError) as e:
            print(str(e), file=sys.stderr)
            return 2
        merged = merge_traces(traces, names=args.paths)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(merged, f)
            print(f"merged {len(args.paths)} traces "
                  f"({len(merged['traceEvents'])} events) -> {args.out}")
        if args.trace:
            rows = request_timeline(merged, args.trace)
            if args.json:
                print(json.dumps(rows, indent=1))
            else:
                print(render_timeline(rows, args.trace))
        elif not args.out:
            if args.json:
                print(json.dumps(merged))
            else:
                print(f"merged {len(args.paths)} traces "
                      f"({len(merged['traceEvents'])} events); use --out "
                      f"to save or --trace ID for a request timeline")
        return 0

    try:
        trace = load_trace(args.paths[0])
    except (FileNotFoundError, json.JSONDecodeError) as e:
        print(str(e), file=sys.stderr)
        return 2

    log_dir = args.log_dir
    if log_dir is None:
        log_dir = args.paths[0] if os.path.isdir(args.paths[0]) \
            else os.path.dirname(os.path.abspath(args.paths[0]))
    trend = goodput_trend(log_dir)

    if args.json:
        print(json.dumps({
            "goodput": goodput_breakdown(trace),
            "coverage": coverage(trace),
            "dropped_events":
                (trace.get("otherData") or {}).get("dropped_events", 0),
            "top_spans": top_spans(trace, args.top),
            "recompile_timeline": recompile_timeline(trace),
            "straggler_timeline": straggler_timeline(trace),
            "goodput_trend": trend,
        }, indent=1))
        return 0

    print(render(trace, args.top, trend))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:         # e.g. piped into head
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
