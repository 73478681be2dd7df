"""Span tracing, goodput accounting, and straggler/recompile diagnostics.

Motivation (MegaScale, arXiv:2402.15627 §5; Megatron-LM scaling,
arXiv:2104.04473): telemetry (telemetry.py) tells you *how fast* the run
is; it does not tell you *where the wall-clock went*, *which host is
slow*, or *why step time spiked*.  This module is that attribution
layer — host-side only, nothing enters the jitted step:

* **SpanTracer** — a thread-safe, ring-buffered span recorder with a
  context-manager API (``with tracer.span("checkpoint_save",
  "checkpoint"): ...``) and Chrome ``trace_event`` JSON export, loadable
  in Perfetto (ui.perfetto.dev) or ``chrome://tracing``.  The training
  loop, checkpointing, resilience rewinds, eval, and data iteration all
  open spans; the whole run nests under one root ``train`` span so the
  trace covers (essentially) every second of wall-clock.

* **GoodputAccounter** — classifies wall-clock into
  productive-``step`` / ``compile`` / ``checkpoint`` / ``eval`` /
  ``rewind`` (restart-recovery) / ``data`` (input stall) / other, fed by
  span closes (outermost goodput-category span wins, so nesting never
  double-counts).  ``goodput_pct`` = productive step seconds over total
  wall seconds — MegaScale's headline reliability metric — and surfaces
  in the JSONL stream, ``run_summary()`` and the wandb/TB finish
  summary.

* **CompileLedger** — the module's ONE ``jax.monitoring`` listener,
  installed when the module is imported and always on: every trace
  (``jaxpr_trace_duration``), lowering (``jaxpr_to_mlir_module_duration``),
  backend compile (``backend_compile_duration``) and persistent-cache load
  (``cache_retrieval_time_sec``) is kept as an event ``(kind, program,
  start, end, thread)`` on ``perf_counter`` and summed by program.  An
  inner jit is traced inside its caller's trace and a cache load lies
  inside its backend "compile", so seconds over a stretch of time are
  the UNION of the events' intervals (``union_secs``), never their sum;
  a program's row keeps its own sums.  Its cost is per compile event: a
  steady state has none.

* **RecompileDetector** — a reader of the ledger: every backend compile
  it hears is timestamped and named; compiles after ``mark_steady()``
  (the loop calls it once the first step has compiled) are *recompiles*
  — the silent step-time killer (a shape or layout leak retraces the
  whole step).  Recompiles count in ``counters['recompiles']`` and emit
  trace spans + flight-recorder entries that carry the program's name.

* **The start-up timeline** — ``startup_span(name)`` around each piece
  of host work between process start and "ready" (imports, initialize,
  build_model, ... warmup / first_step), on the same ``perf_counter``
  as the launch ring and the request spans.  ``startup_ready()`` closes
  it, prints one line and writes one ``startup`` JSONL record: each
  top-level span's seconds, the ledger's union seconds by kind, and the
  programs with the most trace + lower seconds with their counts.

* **StragglerDetector** — at log boundaries the driver allgathers
  per-host section times (the ``timers.py`` ``process_allgather`` path)
  and hands them here; any host exceeding ``threshold`` x the median is
  flagged as a structured straggler event (trace instant + flight
  recorder + ``counters['straggler_events']`` + a printed line).
  Single-host runs can never flag (median of one).

``tools/trace_report.py`` renders the goodput breakdown, top-N slowest
spans, and the recompile/straggler timelines from the exported trace
(plus the JSONL stream) — pure stdlib, runs anywhere the files do.

Collective discipline matches the rest of the codebase: nothing here
performs a collective; the straggler gather happens in the caller at
deterministic log boundaries only (see ``timers.Timers``).
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from statistics import median
from typing import Any, Dict, List, Optional

import jax

from megatron_llm_tpu.global_vars import get_counters

# wall-clock categories the goodput accounting attributes time to; spans
# in any other category (e.g. the root "run" span) are trace-only
GOODPUT_CATEGORIES = ("step", "compile", "checkpoint", "eval", "rewind",
                      "data")
_GOODPUT_SET = frozenset(GOODPUT_CATEGORIES)

TRACE_FILENAME = "trace.json"


# ---------------------------------------------------------------------------
# Goodput accounting
# ---------------------------------------------------------------------------

class GoodputAccounter:
    """Seconds of wall-clock per category + the goodput ratio.

    ``clock`` is injectable for tests; production uses ``perf_counter``
    so the wall denominator and the span durations share a clock."""

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._t0 = clock()
        self._secs: Dict[str, float] = {c: 0.0 for c in GOODPUT_CATEGORIES}
        self._lock = threading.Lock()
        # multi-slice: seconds the fleet spent waiting on each slice
        # (fed from the per-slice step-time lag at log boundaries) — the
        # slice dimension of goodput, aggregated offline by
        # tools/telemetry_report.py
        self._slice_stall: Dict[int, float] = {}

    def add_slice_stall(self, slice_id: int, secs: float) -> None:
        """Attribute fleet wait time to the slice that caused it (its
        step-time lag over the median of the others)."""
        with self._lock:
            self._slice_stall[int(slice_id)] = \
                self._slice_stall.get(int(slice_id), 0.0) \
                + max(float(secs), 0.0)

    def add(self, category: str, secs: float) -> None:
        with self._lock:
            self._secs[category] = self._secs.get(category, 0.0) \
                + max(float(secs), 0.0)

    def move(self, src: str, dst: str, secs: float) -> float:
        """Reattribute up to ``secs`` from ``src`` to ``dst`` (e.g. a
        compile observed inside a step span belongs to 'compile', not
        'step').  Clamped at what ``src`` holds; returns the moved
        amount."""
        with self._lock:
            m = min(max(float(secs), 0.0), self._secs.get(src, 0.0))
            self._secs[src] -= m
            self._secs[dst] = self._secs.get(dst, 0.0) + m
            return m

    def wall_secs(self) -> float:
        return max(self._clock() - self._t0, 1e-9)

    def summary(self) -> Dict[str, float]:
        """Per-category seconds, the unattributed remainder, and
        ``goodput_pct`` (productive-step share of total wall-clock)."""
        wall = self.wall_secs()
        with self._lock:
            secs = dict(self._secs)
        out = {f"{c}_secs": secs.get(c, 0.0) for c in GOODPUT_CATEGORIES}
        out["other_secs"] = max(wall - sum(secs.values()), 0.0)
        out["wall_secs"] = wall
        out["goodput_pct"] = 100.0 * secs.get("step", 0.0) / wall
        with self._lock:
            if self._slice_stall:
                out["slice_stall_secs"] = {
                    str(s): round(v, 6)
                    for s, v in sorted(self._slice_stall.items())}
        return out


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------

class _SpanHandle:
    """Yielded by ``span()`` so the body can attach attributes
    (``s.args["bytes"] = n``) that land in the trace event."""

    __slots__ = ("name", "category", "args")

    def __init__(self, name: str, category: str, args: Dict[str, Any]):
        self.name = name
        self.category = category
        self.args = args


class SpanTracer:
    """Thread-safe ring buffer of Chrome ``trace_event`` records.

    Durations ride ``perf_counter``; the epoch offset is stamped once so
    the export also carries absolute time.  The ring (``capacity``
    events) bounds memory on long runs — eviction drops the *oldest*
    events and counts them in ``dropped``, so a multi-day run keeps its
    freshest history like the flight recorder does."""

    def __init__(self, capacity: int = 100_000):
        self.capacity = max(int(capacity), 1)
        self.goodput = GoodputAccounter()
        self.dropped = 0
        self._events: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._t0 = time.perf_counter()
        self._unix0 = time.time()

    # -- recording ------------------------------------------------------

    def _stack(self) -> List[_SpanHandle]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(ev)

    @contextmanager
    def span(self, name: str, category: str = "other", **attrs):
        """Record one complete ('X') event around the body.  Goodput is
        fed by the *outermost* span whose category is a goodput
        category, so nested phases (a checkpoint_write inside a
        checkpoint_save inside an eval) never double-count."""
        stack = self._stack()
        enclosed = any(s.category in _GOODPUT_SET for s in stack)
        h = _SpanHandle(name, category, dict(attrs))
        stack.append(h)
        start = time.perf_counter()
        try:
            yield h
        finally:
            dur = time.perf_counter() - start
            stack.pop()
            counted = category in _GOODPUT_SET and not enclosed
            if counted:
                self.goodput.add(category, dur)
                h.args["goodput"] = category
            self._append({
                "ph": "X", "name": name, "cat": category,
                "ts": (start - self._t0) * 1e6, "dur": dur * 1e6,
                "tid": threading.get_ident(), "args": h.args,
            })

    def completed(self, name: str, category: str, start: float,
                  dur_secs: float, **attrs) -> None:
        """Record an already-finished interval (``start`` on the
        perf_counter clock) — how the recompile listener logs a compile
        it only hears about at its end."""
        self._append({
            "ph": "X", "name": name, "cat": category,
            "ts": (start - self._t0) * 1e6,
            "dur": max(dur_secs, 0.0) * 1e6,
            "tid": threading.get_ident(), "args": dict(attrs),
        })

    def instant(self, name: str, category: str = "other", **attrs) -> None:
        """A zero-duration marker ('i' event — Perfetto draws a flag)."""
        self._append({
            "ph": "i", "name": name, "cat": category, "s": "p",
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "tid": threading.get_ident(), "args": dict(attrs),
        })

    def __len__(self) -> int:
        return len(self._events)

    # -- export ---------------------------------------------------------

    def chrome_trace(self, reason: str = "") -> Dict[str, Any]:
        """The Chrome ``trace_event`` JSON object (Perfetto-loadable)."""
        try:
            pid = jax.process_index()
        except Exception:
            pid = 0
        with self._lock:
            events = list(self._events)
        # map raw thread idents to small tids + name metadata rows
        names = {t.ident: t.name for t in threading.enumerate()}
        tids: Dict[int, int] = {}
        out_events: List[Dict[str, Any]] = [{
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": f"host{pid}"},
        }]
        for ev in events:
            ident = ev["tid"]
            if ident not in tids:
                tids[ident] = len(tids)
                out_events.append({
                    "ph": "M", "name": "thread_name", "pid": pid,
                    "tid": tids[ident],
                    "args": {"name": names.get(ident, f"thread-{ident}")},
                })
            out_events.append({**ev, "pid": pid, "tid": tids[ident]})
        return {
            "displayTimeUnit": "ms",
            "otherData": {
                "reason": reason,
                "process_index": pid,
                "trace_start_unix": self._unix0,
                "dropped_events": self.dropped,
                "goodput": self.goodput.summary(),
                "recompiles": int(get_counters().get("recompiles", 0)),
                "straggler_events":
                    int(get_counters().get("straggler_events", 0)),
            },
            "traceEvents": out_events,
        }

    def write(self, path: str, reason: str = "") -> str:
        """Atomic (tmp + rename): the caller may be a watchdog thread
        racing ``os._exit``."""
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(reason=reason), f)
        os.replace(tmp, path)
        return path


# ---------------------------------------------------------------------------
# The compile ledger
# ---------------------------------------------------------------------------

COMPILE_KINDS = ("trace", "lower", "backend", "cache_load")
# jax reports a "trace" for every call of a jitted function that misses
# the C++ dispatch cache, a hit in its trace cache too: some 10 us for a
# small function and a few tens for a program of hundreds of arguments,
# where the smallest function traced anew takes 300-500.  A trace shorter
# than this is such a hit: it counts in its program's row (``trace_hit``)
# and is no event
TRACE_HIT_SECS = 250e-6
_KIND_INDEX = {k: i for i, k in enumerate(COMPILE_KINDS + ("trace_hit",))}
# the jax.monitoring duration events the ledger keeps, by its kinds (a
# backend event fires on shape-change retraces too, and on a hit in the
# persistent cache, where it is the load and a few milliseconds more)
_EVENT_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


def union_secs(intervals) -> float:
    """Seconds covered by ``(start, end)`` intervals, each instant once:
    a trace inside a trace, or a cache load inside its backend
    "compile", counts once."""
    covered, hi = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > hi:
            covered += e - max(s, hi)
            hi = e
    return covered


class CompileLedger:
    """What jax traced, lowered, compiled and loaded from its persistent
    cache, heard from ``jax.monitoring``: a bounded list of events
    ``(kind, program, start, end, thread)`` on ``perf_counter`` (the
    first ``capacity``; later ones still count in the rows and in
    ``dropped``), and a row per program: count and seconds of each kind
    (and of ``trace_hit``: see ``TRACE_HIT_SECS``).
    A row's seconds are its events' own sums (an outer program's trace
    includes its inner jits'); over a stretch of time ask ``secs()``,
    which is a union.

    ``hear`` runs on the compiling thread as the timed block ends, so it
    takes no lock and formats nothing: an append and a dictionary update
    under the GIL."""

    def __init__(self, capacity: int = 65_536):
        self.capacity = int(capacity)
        self.events: List[tuple] = []
        self.dropped = 0
        # program -> [count, seconds] per kind, in _KIND_INDEX's order
        self.programs: Dict[str, List[float]] = {}
        self.cache_hits = 0
        self.cache_misses = 0
        # thread -> index in ``events`` of a cache load that waits for
        # the name of the backend event it lies inside
        self._loads: Dict[int, int] = {}
        # called with every event as hear() was: the launch ring
        self.listeners: tuple = ()

    def hear(self, kind: str, name: str, duration: float) -> None:
        end = time.perf_counter()
        if kind == "trace" and duration < TRACE_HIT_SECS:
            self._count("trace_hit", name, duration)
            return
        start = end - duration
        tid = threading.get_ident()
        events = self.events
        if len(events) < self.capacity:
            if kind == "cache_load":
                # nameless ("" here): it takes the name of the backend
                # event that follows it on its thread
                self._loads[tid] = len(events)
            events.append((kind, name, start, end, tid))
        else:
            self.dropped += 1
        if kind != "cache_load":
            self._count(kind, name, duration)
            if kind == "backend":
                at = self._loads.pop(tid, None)
                if at is not None:
                    _, _, s, e, _ = events[at]
                    events[at] = ("cache_load", name, s, e, tid)
                    self._count("cache_load", name, e - s)
        for fn in self.listeners:
            fn(kind, start, end, tid)

    def _count(self, kind: str, name: str, duration: float) -> None:
        row = self.programs.get(name)
        if row is None:
            row = self.programs[name] = [0, 0.0] * len(_KIND_INDEX)
        i = 2 * _KIND_INDEX[kind]
        row[i] += 1
        row[i + 1] += duration

    # -- reading (any thread) -------------------------------------------

    def between(self, t0: float, t1: float) -> List[tuple]:
        """The events that overlap ``[t0, t1]``, oldest end first."""
        return [ev for ev in list(self.events)
                if ev[3] > t0 and ev[2] < t1]

    @staticmethod
    def secs(events, t0: float = float("-inf"), t1: float = float("inf"),
             kinds=None) -> float:
        """Union seconds of ``events`` (of ``kinds``) inside
        ``[t0, t1]``, on the wall clock."""
        return union_secs(
            (max(s, t0), min(e, t1)) for kind, _, s, e, _ in events
            if e > t0 and s < t1 and (kinds is None or kind in kinds))

    def rows(self) -> Dict[str, Dict[str, float]]:
        """A JSON-able copy of the rows: ``{program: {trace: n,
        trace_secs: s, lower: ..., backend: ..., cache_load: ...}}``."""
        out = {}
        for name, row in list(self.programs.items()):
            out[name] = r = {}
            for kind, i in _KIND_INDEX.items():
                r[kind] = int(row[2 * i])
                r[kind + "_secs"] = float(row[2 * i + 1])
        return out


def top_programs(events, n: int = 5) -> List[Dict[str, Any]]:
    """The ``n`` programs with the most trace + lower seconds among
    ``events``, with how many times each was traced and lowered."""
    rows: Dict[str, List[float]] = {}
    for kind, name, s, e, _ in events:
        if kind in ("trace", "lower"):
            row = rows.setdefault(name, [0, 0, 0.0])
            row[kind == "lower"] += 1
            row[2] += e - s
    top = sorted(rows.items(), key=lambda kv: -kv[1][2])[:n]
    return [{"program": name, "traced": int(r[0]), "lowered": int(r[1]),
             "trace_lower_secs": round(r[2], 6)} for name, r in top]


_LEDGER = CompileLedger()


def compile_ledger() -> CompileLedger:
    return _LEDGER


# ---------------------------------------------------------------------------
# Recompile detection
# ---------------------------------------------------------------------------

class RecompileDetector:
    """Counts and timestamps the backend compiles the ledger hears while
    it is installed; compiles after ``mark_steady()`` are recompiles
    (MegaScale's "why did step time spike" class) and carry the
    program's name.  ``pause()``/``resume()`` bracket phases where a
    fresh compile is *expected* (eval's forward-only program, a skipped
    iteration's program) so they never count as recompiles."""

    def __init__(self, tracer: Optional[SpanTracer] = None,
                 max_events: int = 256):
        self.tracer = tracer
        self.compiles = 0                   # every compile heard
        self.recompiles = 0                 # compiles while steady
        self.compile_secs_total = 0.0
        self.events: deque = deque(maxlen=max(int(max_events), 1))
        self._steady = False
        self._paused = 0
        self._pending_n = 0
        self._pending_secs = 0.0
        self._lock = threading.Lock()

    def on_compile(self, duration_secs: float, program: str = "") -> None:
        """Called by the ledger's listener at each backend-compile
        completion."""
        now = time.perf_counter()
        with self._lock:
            if self._paused:
                return
            self.compiles += 1
            self.compile_secs_total += duration_secs
            self._pending_n += 1
            self._pending_secs += duration_secs
            is_recompile = self._steady
            if is_recompile:
                self.recompiles += 1
                get_counters()["recompiles"] += 1
                self.events.append({
                    "kind": "recompile", "secs": float(duration_secs),
                    "program": program, "time_unix": time.time(),
                })
        if self.tracer is not None:
            self.tracer.completed(
                "recompile" if is_recompile else "backend_compile",
                "compile", start=now - duration_secs,
                dur_secs=duration_secs, program=program)
        if is_recompile:
            print(f" [tracing] RECOMPILE detected: backend compile of "
                  f"{program or '?'} {duration_secs:.2f}s after steady "
                  f"state — a shape/layout change retraced the step",
                  flush=True)
            try:
                from megatron_llm_tpu import telemetry

                fr = telemetry.get_flight_recorder()
                if fr is not None:
                    fr.record({"kind": "recompile", "time_unix": time.time(),
                               "secs": float(duration_secs),
                               "program": program})
            except Exception:
                pass

    # -- driver hooks ---------------------------------------------------

    def mark_steady(self) -> None:
        """The first step has compiled; compiles from here on are
        recompiles."""
        self._steady = True

    def pause(self) -> None:
        with self._lock:
            self._paused += 1

    def resume(self) -> None:
        with self._lock:
            self._paused = max(self._paused - 1, 0)

    def drain(self):
        """(count, seconds) of compiles since the last drain — the loop
        uses this to reattribute a step span's compile time to the
        'compile' goodput category."""
        with self._lock:
            n, secs = self._pending_n, self._pending_secs
            self._pending_n, self._pending_secs = 0, 0.0
        return n, secs


# One listener forever (jax.monitoring has no unregister), registered as
# the module is imported: it feeds the ledger, and hands a backend
# compile to whichever detector is currently installed, so tests can
# install/uninstall freely.
_ACTIVE_DETECTOR: Optional[RecompileDetector] = None


def _monitor_callback(event: str, duration: float, **kw) -> None:
    kind = _EVENT_KINDS.get(event)
    if kind is None:
        return
    try:
        # a trace is reported under the function's name, its lowering
        # and compile under the module's, ``jit(<name>)``
        name = kw.get("fun_name") or ""
        if name.endswith(")"):
            name = name[name.find("(") + 1:-1]
        duration = float(duration)
        _LEDGER.hear(kind, name, duration)
        d = _ACTIVE_DETECTOR
        if d is not None and kind == "backend":
            d.on_compile(duration, name)
    except Exception:
        pass                        # diagnostics must never break a compile


def _monitor_event(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _LEDGER.cache_hits += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _LEDGER.cache_misses += 1


jax.monitoring.register_event_duration_secs_listener(_monitor_callback)
jax.monitoring.register_event_listener(_monitor_event)


def install_detector(detector: Optional[RecompileDetector]) -> None:
    global _ACTIVE_DETECTOR
    _ACTIVE_DETECTOR = detector


# ---------------------------------------------------------------------------
# The start-up timeline
# ---------------------------------------------------------------------------

# the spans of the start-up under way, ``(name, t0, t1, fields)`` on
# perf_counter, and the last one that reached "ready" (what
# ``startup_timeline()`` gives): a few dozen entries a process, always on
_STARTUP: List[tuple] = []
_STARTUP_ROOM = 512
_STARTUP_DONE: Optional[Dict[str, Any]] = None


def startup_begin() -> None:
    """A start-up begins: forget the spans of an earlier one that never
    got ready (an engine built and never started)."""
    del _STARTUP[:]


def startup_completed(name: str, t0: float, t1: float, **fields) -> None:
    """An already-finished piece of the start-up.  An entry module
    hands in ``imports`` this way, after its import block, from the
    ``perf_counter`` stamp its FIRST statement took (this module imports
    jax, so it cannot take that stamp itself)."""
    if len(_STARTUP) < _STARTUP_ROOM:
        _STARTUP.append((name, t0, t1, fields))


@contextmanager
def startup_span(name: str, **fields):
    """A piece of the start-up, host side: kept on the timeline, which
    at "ready" also goes to the SpanTracer where one is installed, under
    category ``startup`` (the first pieces end before there is one)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        startup_completed(name, t0, time.perf_counter(), **fields)


def _top_level(spans: List[tuple]) -> List[tuple]:
    """The spans that lie inside no other."""
    return [a for a in spans
            if not any(b is not a and b[1] <= a[1] and a[2] <= b[2]
                       and (b[2] - b[1]) > (a[2] - a[1]) for b in spans)]


def startup_ready(printer=print) -> Optional[Dict[str, Any]]:
    """The program can serve or step: close the timeline at this
    instant, print its one line, write its ``startup`` record to the
    JSONL stream and keep it for ``startup_timeline()``.  None (and
    nothing printed) when no span was opened since the last one."""
    global _STARTUP_DONE
    if not _STARTUP:
        return None
    ready = time.perf_counter()
    spans = sorted(_STARTUP, key=lambda s: (s[1], -s[2]))
    del _STARTUP[:]
    first = spans[0][1]
    events = _LEDGER.between(first, ready)
    secs = {k: round(_LEDGER.secs(events, first, ready, (k,)), 6)
            for k in COMPILE_KINDS}
    summary = {
        "wall_secs": round(ready - first, 6),
        "spans": {}, "children": {}, "compile_secs": secs,
        "compile_union_secs": round(_LEDGER.secs(events, first, ready), 6),
        "top_programs": top_programs(events),
        "cache_hits": _LEDGER.cache_hits,
        "cache_misses": _LEDGER.cache_misses,
    }
    top = _top_level(spans)
    for span in spans:
        name, t0, t1, _ = span
        into = summary["spans" if span in top else "children"]
        into[name] = round(into.get(name, 0.0) + t1 - t0, 6)
    _STARTUP_DONE = {"first": first, "ready": ready, "spans": spans,
                     "events": events, "summary": summary}
    t = _ACTIVE
    if t is not None:
        for name, t0, t1, fields in spans:
            t.tracer.completed(name, "startup", start=t0, dur_secs=t1 - t0,
                               **fields)
    if printer is not None:
        printer(startup_line(summary))
    try:
        from megatron_llm_tpu import telemetry

        stream = telemetry.get_stream()
        if stream is not None:
            stream.emit({"kind": "startup", **summary})
    except Exception:
        pass
    instant("ready", "startup")
    return summary


def startup_line(summary: Dict[str, Any]) -> str:
    """The one line printed at "ready"."""
    spans = " ".join(f"{n} {s:.2f}" for n, s in summary["spans"].items())
    kinds = " ".join(f"{k} {s:.2f}"
                     for k, s in summary["compile_secs"].items())
    top = ", ".join(
        f"{p['program']} x{p['traced']}/{p['lowered']} "
        f"{p['trace_lower_secs']:.2f} s" for p in summary["top_programs"])
    return (f" [startup] ready after {summary['wall_secs']:.2f} s | "
            f"spans (s): {spans} | compile union (s): {kinds} | most "
            f"trace + lower (traced/lowered): {top or '-'}")


def startup_timeline() -> Optional[Dict[str, Any]]:
    """The last start-up that reached "ready": ``first`` (its first
    stamp) and ``ready`` on perf_counter, its ``spans`` ``(name, t0, t1,
    fields)`` by start, the ledger's ``events`` between the two, and the
    ``summary`` that was printed.  None before any."""
    return _STARTUP_DONE


def startup_summary() -> Optional[Dict[str, Any]]:
    done = _STARTUP_DONE
    return done["summary"] if done is not None else None


# ---------------------------------------------------------------------------
# Straggler detection
# ---------------------------------------------------------------------------

class StragglerDetector:
    """Flags hosts whose per-section time exceeds ``threshold`` x the
    cross-host median (MegaScale §5.2's automated straggler hunt).  The
    caller supplies already-gathered per-host values (the ``timers.py``
    ``process_allgather`` path) at deterministic log boundaries — this
    class performs no collective itself."""

    def __init__(self, threshold: float = 1.5, min_secs: float = 0.005,
                 tracer: Optional[SpanTracer] = None,
                 max_events: int = 256,
                 printer=print,
                 host_slice_map: Optional[List[int]] = None):
        self.threshold = float(threshold)
        self.min_secs = float(min_secs)     # ignore sub-noise spreads
        self.tracer = tracer
        self.printer = printer
        self.events: deque = deque(maxlen=max(int(max_events), 1))
        self.total = 0
        # host index -> slice id (multislice.host_slice_map); when set,
        # every event names the slice the straggling host belongs to —
        # the MegaScale "which slice is the fleet waiting on" dimension
        self.host_slice_map = host_slice_map

    def check(self, per_host: Dict[str, List[float]],
              iteration: int) -> List[Dict[str, Any]]:
        """One boundary's straggler scan; returns (and records) the
        structured events.  ``per_host`` maps section name -> one value
        per host (e.g. ``timers.report()``'s gathered snapshot)."""
        found: List[Dict[str, Any]] = []
        for section in sorted(per_host):
            values = per_host[section]
            if len(values) < 2:
                continue                    # single host: no medians to lag
            med = median(values)
            if med <= 0:
                continue
            for host, v in enumerate(values):
                if v > self.threshold * med and (v - med) >= self.min_secs:
                    ev = {
                        "kind": "straggler", "iteration": int(iteration),
                        "section": section, "host": int(host),
                        "secs": float(v), "median_secs": float(med),
                        "ratio": float(v / med),
                        "time_unix": time.time(),
                    }
                    hsm = self.host_slice_map
                    if hsm is not None and host < len(hsm):
                        ev["slice"] = int(hsm[host])
                    found.append(ev)
        if found:
            self.total += len(found)
            get_counters()["straggler_events"] += len(found)
            for ev in found:
                self.events.append(ev)
                if self.tracer is not None:
                    keys = ("iteration", "section", "host",
                            "secs", "median_secs", "ratio")
                    if "slice" in ev:
                        keys = keys + ("slice",)
                    self.tracer.instant("straggler", "straggler",
                                        **{k: ev[k] for k in keys})
                who = (f"slice {ev['slice']} host {ev['host']}"
                       if "slice" in ev else f"host {ev['host']}")
                self.printer(
                    f" [tracing] STRAGGLER {who} at iteration "
                    f"{ev['iteration']}: {ev['section']} "
                    f"{ev['secs'] * 1000:.1f} ms = {ev['ratio']:.2f}x the "
                    f"median ({ev['median_secs'] * 1000:.1f} ms)")
            try:
                from megatron_llm_tpu import telemetry

                fr = telemetry.get_flight_recorder()
                if fr is not None:
                    for ev in found:
                        fr.record(dict(ev))
            except Exception:
                pass
        return found


# ---------------------------------------------------------------------------
# Bundle + CLI wiring + module-level access
# ---------------------------------------------------------------------------

@dataclass
class Tracing:
    """Everything the observability layer needs, in one bundle."""

    tracer: SpanTracer
    recompile: Optional[RecompileDetector] = None
    straggler: Optional[StragglerDetector] = None
    trace_dir: Optional[str] = None

    def goodput_summary(self) -> Dict[str, float]:
        return self.tracer.goodput.summary()

    def trace_path(self) -> Optional[str]:
        if not self.trace_dir:
            return None
        try:
            idx = jax.process_index()
        except Exception:
            idx = 0
        name = TRACE_FILENAME if idx == 0 else f"trace_p{idx}.json"
        return os.path.join(self.trace_dir, name)

    def write_trace(self, reason: str = "") -> Optional[str]:
        path = self.trace_path()
        if path is None:
            return None
        os.makedirs(self.trace_dir, exist_ok=True)
        return self.tracer.write(path, reason=reason)

    def close(self) -> None:
        try:
            self.write_trace(reason="close")
        except Exception:
            pass
        if get_tracing() is self:
            install_tracing(None)


_ACTIVE: Optional[Tracing] = None


def install_tracing(tracing: Optional[Tracing]) -> None:
    """Register the run's Tracing so checkpointing/resilience/telemetry
    reach it without threading it through every call chain (same pattern
    as telemetry.install_stream)."""
    global _ACTIVE
    _ACTIVE = tracing
    install_detector(tracing.recompile if tracing is not None else None)


def get_tracing() -> Optional[Tracing]:
    return _ACTIVE


def get_tracer() -> Optional[SpanTracer]:
    return _ACTIVE.tracer if _ACTIVE is not None else None


@contextmanager
def span(name: str, category: str = "other", **attrs):
    """Module-level span that no-ops when no tracer is installed — how
    checkpointing / resilience / the train loop open spans without
    caring whether tracing is on."""
    t = _ACTIVE
    if t is None:
        yield None
        return
    with t.tracer.span(name, category, **attrs) as h:
        yield h


def instant(name: str, category: str = "other", **attrs) -> None:
    t = _ACTIVE
    if t is not None:
        t.tracer.instant(name, category, **attrs)


def goodput_summary() -> Optional[Dict[str, float]]:
    return _ACTIVE.goodput_summary() if _ACTIVE is not None else None


def dump_trace(reason: str = "") -> Optional[str]:
    """Write the active trace (crash/watchdog path — never raises)."""
    try:
        if _ACTIVE is None:
            return None
        return _ACTIVE.write_trace(reason=reason)
    except Exception:
        return None


def new_trace_id() -> str:
    """A fleet-unique request trace id (the ``X-Request-Trace`` value).
    16 hex chars: short enough to read in logs, unique enough for any
    realistic request volume.  The serving router mints one per inbound
    request; replicas mint their own only for direct (router-less)
    traffic."""
    return uuid.uuid4().hex[:16]


def start_trace_flusher(bundle: Tracing,
                        interval_secs: float = 5.0) -> threading.Thread:
    """Periodically write ``bundle``'s trace file from a daemon thread.

    Long-lived serving processes never reach the trainer's clean
    ``close()`` boundary — without a flusher the Chrome trace only
    exists after graceful shutdown, which is exactly when you don't
    need it.  The returned thread carries a ``stop`` Event; set it (and
    optionally join) to stop flushing."""
    stop = threading.Event()

    def loop():
        while not stop.wait(interval_secs):
            try:
                bundle.write_trace(reason="periodic")
            except Exception:
                pass

    t = threading.Thread(target=loop, name="trace-flusher", daemon=True)
    t.stop = stop           # type: ignore[attr-defined]
    t.start()
    return t


def build_tracing(args) -> Optional[Tracing]:
    """CLI wiring: a Tracing bundle from parsed args, or None when
    ``--trace_dir`` is unset."""
    trace_dir = getattr(args, "trace_dir", None)
    if not trace_dir:
        return None
    tracer = SpanTracer(
        capacity=getattr(args, "trace_buffer_size", 100_000) or 100_000)
    t = Tracing(
        tracer=tracer,
        recompile=RecompileDetector(tracer=tracer),
        straggler=StragglerDetector(
            threshold=getattr(args, "straggler_threshold", 1.5) or 1.5,
            tracer=tracer),
        trace_dir=trace_dir,
    )
    install_tracing(t)
    return t
