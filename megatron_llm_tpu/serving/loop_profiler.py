"""Engine-loop span stream: one record per launch, on two clocks.

Every launch of the engine's jitted programs (prefill chunk / decode
step / verify step) is one ``DispatchRecord``: its number (``seq``), its
``kind``, what it worked on, and the absolute ``time.perf_counter``
stamp of ``begin`` and of every phase boundary.  The phases tile the
launch —

* ``schedule``     deadline sweep, admission, preemption, the decision,
* ``draft``        prompt-lookup proposals (speculative only),
* ``build_inputs`` host-numpy array assembly + COW barriers + swap-ins,
* ``dispatch``     the jitted call, until it RETURNS: argument upload
                   and enqueue (a last prefill chunk's first-token
                   sampler call included),
* ``fetch``        until the last of the launch's results is home (they
                   set out together as the launch returns),
* ``emit``         token commits, stream writes, retirement, telemetry,

— and ``gap`` is the time between one launch's ``finish`` and the next
``begin``.  ``dispatch`` + ``fetch`` is what the host WAITED
(``wait_secs`` / ``wait_pct``); it is a host clock and is never called
device time.  ``host_bubble_pct`` = 100 - ``wait_pct`` is the share of
the loop the device cannot be running the engine's work, which is the
before/after baseline any double-buffering of the loop must beat.

The record is the serve loop's ONE span source:

* a bounded ring (``RING_SIZE`` launches) holds the records themselves,
  so a reader can cut them to a window or lay them against a profiler
  trace; ``live_profilers()`` reaches it without the engine;
* each phase is also a ``jax.profiler.TraceAnnotation`` named
  ``loop.<phase>`` (``loop.gap`` between launches), entered and left at
  the marks on the engine thread with ``seq`` (and ``kind`` once the
  scheduler has decided) as arguments: inert with no profiler
  recording, and with one (``--profile_dir``, TensorBoard) the loop's
  phases sit on the host line above the device's operations, on the
  profiler's clock;
* with a ``tracing.SpanTracer`` installed (``--trace_dir``) ``finish``
  writes the same record once more as Chrome-trace spans: the
  ``loop.<phase>`` sub-spans and the enclosing ``decode_step`` /
  ``prefill_chunk`` span (start of ``dispatch`` to end of ``fetch``)
  that ``tools/serve_report.py`` joins a request's lifecycle on;
* ``record_request`` keeps each retired request's own span beside the
  launches (submit, admit, first token, finish on the same clock);
* ``programs`` holds, beside the ring, an instruction table of each
  compiled program behind the launches (``hlo_collectives.ProgramTable``,
  by the program's name: ``LAUNCH_PROGRAMS`` says which a launch of each
  kind runs), so that a device operation of a profiler's trace that
  starts inside a launch can be looked up by its name and given a role
  and, through the launch's record, the requests that caused it.  Empty
  until ``program_tables()`` is asked for: building them reads each
  program's text and is nobody's launch path.  A program with no launch
  ring (the train step) registers its table under ``live_programs()``.

Everything here is host-side python: the profiler never touches a
traced value, so the zero-steady-state-recompile invariant holds with
it on (guarded by ``test_engine_zero_recompiles_after_warmup``).

Other surfaces, as before: cumulative per-phase seconds and mergeable
phase histograms (``stats()``, the engine block of ``/metrics``), a
rollup over the last ``WINDOW_DISPATCHES`` launches, the periodic
``engine_loop_stats`` JSONL record, and the stall detector (armed after
warmup so compile gaps never count): a launch whose gap passes the
threshold, or whose own ``dispatch`` + ``fetch`` passes three times its
kind's running median, with what it lost the time to beside it
(``compile_secs`` from the compile ledger of ``tracing.py``, ``gc_secs``
from the interpreter's collector).
"""

from __future__ import annotations

import gc
import threading
import time
import weakref
from bisect import bisect_left
from collections import deque
from itertools import islice
from statistics import median
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

from megatron_llm_tpu import telemetry, tracing

# Canonical phase order: the order the marks tile a launch in.
LOOP_PHASES = ("schedule", "draft", "build_inputs", "dispatch", "fetch",
               "emit")
_INDEX = {p: i for i, p in enumerate(LOOP_PHASES)}
_DISPATCH, _FETCH = _INDEX["dispatch"], _INDEX["fetch"]
_NOTE_NAMES = tuple("loop." + p for p in LOOP_PHASES)
# the phase that starts when phase i ends (``draft`` only in a verify
# launch; the tail after an ``emit`` mark stays emit's)
_NEXT = {True: (1, 2, 3, 4, 5, 5), False: (2, 2, 3, 4, 5, 5)}

# launches kept: a 45 s window, its lead-in, a traced stretch and a 90 s
# drain at 80 launches a second fit several times over
RING_SIZE = 65536
REQUEST_RING_SIZE = 16384
# the "recent" rollup of stats() (and the alert rule on it), the
# postmortem bundle and the stall detector's running medians look at this
# many launches, whatever the ring holds
WINDOW_DISPATCHES = 512
# a launch is slow when its dispatch + fetch passes this many times the
# median of its kind's over the window, and this many seconds; the
# medians are taken anew every so many launches, from a kind's launches
# in the window if it has this many
SLOW_FACTOR = 3.0
SLOW_MIN_SECS = 0.05
_MEDIANS_EVERY = 256
_MEDIAN_MIN_LAUNCHES = 8

# Host phases run far below DEFAULT_LATENCY_BUCKETS' 1 ms floor, so the
# loop histograms get their own fixed bounds (fleet-mergeable: fixed
# across replicas like every other telemetry histogram).
LOOP_PHASE_BUCKETS = (
    0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
)

# What a launch counts, by who fills it (all 0 where the model has no such
# mechanism).  Each name is an attribute of the launch's DispatchRecord,
# a key of its ``as_dict()`` and a running total of the profiler
# (``LoopProfiler.totals``): a new counter is one name here, filled by
# who knows it, and nothing else (``telemetry.TELEMETRY_SCHEMA_VERSION``
# says why it is no change of schema).
#
# routing of a sparse model's launch, summed over its layers, from the
# histogram its program returns (``ops/paged_kv.py::CachePlan.
# account_routing``): live (token, choice) assignments; experts that
# received at least one; experts there are (layers x E); each layer's
# largest count of assignments to one expert; and of the live
# assignments those that fell on an expert this chip holds (all of them
# unless the layer holds a share of its router's experts:
# ``cfg.moe_router_experts``), and of the experts this chip holds those
# that received at least one: the matrices its grouped matmul reads
MOE_FIELDS = ("moe_assignments", "moe_experts_touched", "moe_expert_slots",
              "moe_busiest_expert_assignments", "moe_assignments_held",
              "moe_experts_touched_held")

# learned sparse attention (a model with an indexer), summed over the
# launch's live queries and the layers (``CachePlan.account``): the keys
# each query sees (its context and itself), and the same with each term
# cut at the top-k, which is what it attends; the choice's work: blocks
# of keys its select steps count over (each step stops at its slot's
# last live block), summed over the launch's steps and the layers, and
# the same had every step counted its slot's whole table
DSA_FIELDS = ("dsa_keys_live", "dsa_keys_selected",
              "dsa_select_blocks_counted", "dsa_select_blocks_table")

# latent attention (a model with a latent pool), summed over the layers
# (``CachePlan.account``): a decode launch's live keys (each live row's
# context and itself: rows of the pool its walk reads), a chunk's
# (query, key) pairs (for each live query the keys it sees), and the
# context tokens (history and chunk) its walk multiplies by the
# up-projection: a chunk on the kernel path, the EXPANDED form; 0 on a
# decode launch and where the dense fallback runs, both absorbed
MLA_FIELDS = ("mla_keys_live", "mla_pairs", "mla_latents_expanded")

# state-space layers (a model with 'mamba' layers; ``CachePlan.account``):
# live rows x layers whose state the launch advances; the rows x layers
# whose state a DECODE launch's program reads and writes (the step's
# kernel, ``ops/pallas/ssm_step.py``: the live rows; the XLA step: every
# slot and the garbage row; 0 on a prefill launch); tokens scanned x
# layers; bytes of state the admitted requests hold in the STATE group
# as the launch begins (slots in use x a slot's state over the layers
# that carry one, whatever their kind)
SSM_FIELDS = ("ssm_rows_live", "ssm_rows_moved", "ssm_tokens",
              "ssm_state_bytes_held")

# gated short-convolution layers (a model with 'conv' layers;
# ``CachePlan.account``): live rows x layers whose columns the launch
# reads and writes; tokens convolved x layers
CONV_FIELDS = ("conv_rows_live", "conv_tokens")

# power-retention layers (a model with 'retention' layers;
# ``CachePlan.account``): live rows x layers; rows x layers whose state a
# DECODE launch's program moves (the step's kernel,
# ``ops/pallas/retention_step.py``: the live rows; the XLA step: every
# slot and the garbage row; 0 on a prefill launch); tokens x layers; and
# of a PREFILL launch's tokens x layers those whose chunk ran in the
# kernel (``ops/pallas/retention_chunk.py``: all of them on the
# ``'pallas'`` path, 0 on XLA's, so the share the kernel took is one
# division).  The state's bytes are ``ssm_state_bytes_held``, the state
# group's
RETENTION_FIELDS = ("retention_rows_live", "retention_rows_moved",
                    "retention_tokens", "retention_chunk_tokens_kernel")

# gated delta-rule layers (a model with 'gated_delta' layers;
# ``CachePlan.account``): live rows x layers; rows x layers whose state a
# DECODE launch's program moves (the step's kernel,
# ``ops/pallas/delta_step.py``: the live rows; the XLA step: every slot
# and the garbage row; 0 on a prefill launch); tokens x layers; and of a
# PREFILL launch's tokens x layers those whose chunk ran in the kernel
# (``ops/pallas/delta_chunk.py``: all of them on the ``'pallas'`` path, 0
# on XLA's, as ``retention_chunk_tokens_kernel``).  The state's bytes are
# ``ssm_state_bytes_held``, the state group's
DELTA_FIELDS = ("delta_rows_live", "delta_rows_moved", "delta_tokens",
                "delta_chunk_tokens_kernel")

# a model with a layer type per layer (the engine's ``_window_advance``):
# window-group pages given back to the allocator before this launch and
# pages it took (each logical page of a context once: what one table a
# slot would have kept); bytes of the pages the running requests hold in
# both groups, the full group's pages among them, and the tokens those
# requests have in the cache, all as the launch begins
KV_FIELDS = ("kv_window_pages_returned", "kv_window_pages_spanned",
             "kv_held_bytes", "kv_full_pages_held", "kv_live_tokens")

# the paged walk (a model whose layers keep pages of K and V;
# ``CachePlan.account``): live rows x layers whose attention walks such a
# pool in the launch, on either path; and of those the walks that
# multiplied in the pool's dtype (the kernel, where its own
# ``ops/pallas/paged_attention.py::native_operands`` of the pool's dtype
# and the queries' says so; 0 on the dense path, which widens what it
# gathers, and under an int8 pool, whose scales make the operands fp32).
# A latent pool's walks are MLA_FIELDS'.  Since PR 62 the VERIFY launch
# asks ``account`` too, so every group above that counts by live rows
# (the selection's, the states') is filled for kind "verify" as for
# "decode", where it read 0 before
WALK_FIELDS = ("walks", "walks_native")

# a looped stack (a model with ``loop_steps`` > 1; ``CachePlan.account``):
# layers x passes x live rows of the launch: the layer bodies its program
# ran for them.  ``walks`` counts a layer A PASS too, so ``walks /
# loop_layer_runs`` is 1 for such a model; 0 for a stack run once
LOOP_FIELDS = ("loop_layer_runs",)

# a prefill chunk's output head (the engine's ``_run_prefill_chunk``): the
# rows it multiplied by the ``[V, H]`` head: 1 for the chunk that ends
# its request's context, whose last live row the first token is sampled
# from, 0 for every other chunk, which runs no head
PREFILL_FIELDS = ("prefill_head_rows",)

# what the launch moved between host and device (the engine's launch
# paths): host arrays handed to its programs (each table counts one; an
# array that lives on the device counts only in the launch that uploads
# it again after a write), and the times the host waited on results
# (those of one wait set out for the host together); and what the host
# lost inside the launch: the union seconds of the compile ledger's
# events (a trace, a lowering, a backend compile, a cache load) that
# ended on the launch's thread between its ``begin`` and its finish, and
# the seconds the interpreter spent in garbage collections that ended
# there (both 0.0 in a sound steady state)
HOST_FIELDS = ("host_uploads", "host_reads", "compile_secs", "gc_secs")

# the ONE declaration of the counted fields: what ``finish`` sums,
# ``as_dict()`` carries and ``totals()`` gives goes through it
COUNTED_FIELDS = (MOE_FIELDS + DSA_FIELDS + MLA_FIELDS + SSM_FIELDS
                  + CONV_FIELDS + RETENTION_FIELDS + DELTA_FIELDS + KV_FIELDS
                  + WALK_FIELDS + LOOP_FIELDS + PREFILL_FIELDS
                  + HOST_FIELDS)

# the compiled programs whose operations run inside a launch of each
# kind (a last prefill chunk samples its first token in the same launch),
# and the page programs, which run while the next launch's inputs are
# built: the keys of ``LoopProfiler.programs``
LAUNCH_PROGRAMS = {"prefill": ("engine_prefill", "engine_sample_first"),
                   "decode": ("engine_decode",),
                   "verify": ("engine_verify",)}
PAGE_PROGRAMS = ("engine_cow_copy", "engine_fetch_block",
                 "engine_host_load")

# the enclosing Chrome-trace span of a launch, by its kind
_SPAN_NAME = {"prefill": "prefill_chunk", "decode": "decode_step",
              "verify": "decode_step"}


class RequestSpan(NamedTuple):
    """One retired request on the launches' clock (``perf_counter``);
    ``admit`` / ``first_token`` are None for a request that never got
    that far."""
    request: int
    trace_id: Optional[str]
    submit: float
    admit: Optional[float]
    first_token: Optional[float]
    finish: float
    prompt_tokens: int
    answer_tokens: int
    finish_reason: Optional[str]


class DispatchRecord:
    """One launch, owned by the engine thread until
    ``LoopProfiler.finish`` and read-only in the ring after it.

    ``t[0]`` is ``begin`` and ``t[i + 1]`` the end of phase
    ``LOOP_PHASES[i]``, all absolute ``perf_counter`` stamps: the phases
    tile ``[t[0], t[-1]]`` exactly and sum to the launch's wall-clock by
    construction.  ``mark(phase)`` ends ``phase`` now; marks come in
    canonical order, a repeated mark extends its phase, and one out of
    order is attributed to the latest phase marked."""

    kind = "decode"
    # what it worked on (class defaults; a launch sets what it has):
    # decode/verify rows and the sum of their context tokens; for a
    # prefill chunk its start and valid (its request is ``requests[0]``)
    rows = 0
    context_tokens = 0
    start = 0
    valid = 0
    cached_tokens = 0
    drafted = 0
    # the sampler's work in a decode/verify launch: live rows that are
    # not greedy (any makes the step draw), and of those the rows with an
    # active top-k or top-p (any makes the step sort)
    sampler_rows_drawn = 0
    sampler_rows_filtered = 0
    # the requests (and their trace ids) this launch served: the spans
    # that caused it
    requests: Tuple[int, ...] = ()
    traces: Tuple[str, ...] = ()
    # what the gap before this launch lost to compiles and collections
    gap_compile_secs = 0.0
    gap_gc_secs = 0.0
    _at = 0                     # index of the last phase marked

    def __init__(self, clock, seq: int, begin: float, gap_secs: float):
        self.seq = seq
        self.gap_secs = gap_secs
        self.t = [begin] * (len(LOOP_PHASES) + 1)
        self._clock = clock
        self._note = note = TraceAnnotation("loop.schedule", seq=seq)
        note.__enter__()

    def mark(self, phase: str) -> None:
        now = self._clock()
        i = _INDEX[phase]
        if i < self._at:
            i = self._at
        else:
            self._at = i
        self.t[i + 1] = now
        self._note.__exit__(None, None, None)
        self._note = note = TraceAnnotation(
            _NOTE_NAMES[_NEXT[self.kind == "verify"][i]], seq=self.seq,
            kind=self.kind)
        note.__enter__()

    def _close(self, now: float) -> None:
        """End the launch at ``now``: the tail goes to ``emit``, and a
        phase never marked takes no time."""
        self._note.__exit__(None, None, None)
        self._note = None
        t = self.t
        t[-1] = now
        for i in range(1, len(t)):
            if t[i] < t[i - 1]:
                t[i] = t[i - 1]

    # -- reading (any thread, after finish) -----------------------------

    @property
    def request(self) -> Optional[int]:
        """The one request a prefill chunk belongs to."""
        return (self.requests[0] if self.kind == "prefill" and self.requests
                else None)

    @property
    def begin(self) -> float:
        return self.t[0]

    @property
    def end(self) -> float:
        return self.t[-1]

    @property
    def wall_secs(self) -> float:
        return self.t[-1] - self.t[0]

    @property
    def wait_secs(self) -> float:
        """``dispatch`` + ``fetch``: what the host waited."""
        return self.t[_FETCH + 1] - self.t[_DISPATCH]

    def phase_start(self, phase: str) -> float:
        return self.t[_INDEX[phase]]

    def phase_end(self, phase: str) -> float:
        return self.t[_INDEX[phase] + 1]

    def phase_secs(self, phase: str) -> float:
        i = _INDEX[phase]
        return self.t[i + 1] - self.t[i]

    def as_dict(self) -> Dict[str, Any]:
        """JSON-able copy (postmortem bundles)."""
        return {
            "seq": self.seq, "kind": self.kind, "begin": self.t[0],
            "wall_secs": self.wall_secs, "gap_secs": self.gap_secs,
            "gap_compile_secs": self.gap_compile_secs,
            "gap_gc_secs": self.gap_gc_secs,
            "wait_secs": self.wait_secs,
            "phases": {p: self.phase_secs(p) for p in LOOP_PHASES},
            "rows": self.rows, "context_tokens": self.context_tokens,
            "request": self.request, "start": self.start,
            "valid": self.valid, "requests": list(self.requests),
            "traces": list(self.traces),
            "sampler_rows_drawn": self.sampler_rows_drawn,
            "sampler_rows_filtered": self.sampler_rows_filtered,
            **{f: getattr(self, f) for f in COUNTED_FIELDS},
        }


# every counted field is an attribute of a record, 0 until who knows it
# fills it (COUNTED_FIELDS says who)
for _f in COUNTED_FIELDS:
    setattr(DispatchRecord, _f, 0.0 if _f.endswith("_secs") else 0)


# The profilers of this process's newest engines, so that a reader which
# was handed no engine (the benchmark's metric sources receive only their
# ``run``, and read after the engine has stopped) reaches the spans.  A
# profiler stays readable here until ``_LIVE.maxlen`` newer ones have
# pushed it out, and through ``program_source`` so does what its engine's
# tables are built from.
_LIVE: deque = deque(maxlen=4)
_LIVE_LOCK = threading.Lock()
# programs that run with no launch ring (the train step), by name: a
# function that builds the program's table, and the table once built
_PROGRAM_SOURCES: Dict[str, Callable[[], Any]] = {}
_PROGRAM_TABLES: Dict[str, Any] = {}


# The profilers there are, weakly, for the two callbacks below: a tuple
# replaced whole when a profiler is made, so that the compile ledger's
# listener (on the compiling thread) and the collector's callback (on
# whichever thread set the collection off) walk it with no lock.
_WATCHING: tuple = ()
_gc_began = 0.0


def _watch(profiler: "LoopProfiler") -> None:
    """Let the two callbacks reach ``profiler``; the first call installs
    them (one listener of the ledger, one ``gc.callbacks`` entry)."""
    global _WATCHING
    with _LIVE_LOCK:
        if not _WATCHING:
            ledger = tracing.compile_ledger()
            ledger.listeners += (_on_compile,)
            gc.callbacks.append(_on_gc)
        _WATCHING = tuple(r for r in _WATCHING if r() is not None) + (
            weakref.ref(profiler),)


def _on_compile(kind: str, start: float, end: float, tid: int) -> None:
    """The ledger heard an event end on thread ``tid``: it is the open
    launch's (or the gap's) of the profiler whose loop runs there."""
    for ref in _WATCHING:
        p = ref()
        if p is not None and p._thread == tid:
            p._credit_compile(start, end)


def _on_gc(phase: str, info: dict) -> None:
    """A collection stops every thread of the interpreter, whichever set
    it off: its seconds go to each profiler's open launch, or to the gap
    it is in."""
    global _gc_began
    if phase == "start":
        _gc_began = time.perf_counter()
        return
    secs = time.perf_counter() - _gc_began
    for ref in _WATCHING:
        p = ref()
        if p is not None:
            d = p._open
            if d is not None:
                d.gc_secs += secs
            else:
                p._gap_gc += secs


def live_profilers() -> List["LoopProfiler"]:
    """The profilers of the newest engines of this process, the one
    with the most launches first."""
    with _LIVE_LOCK:
        found = list(_LIVE)
    return sorted(found, key=lambda p: -p.launches())


def register_program(name: str, build: Callable[[], Any]) -> None:
    """``build()`` gives the instruction table of the program ``name``
    of this process; it is called when ``live_programs()`` is first read
    and not before."""
    with _LIVE_LOCK:
        _PROGRAM_SOURCES[name] = build
        _PROGRAM_TABLES.pop(name, None)


def live_programs() -> Dict[str, Any]:
    """The tables of the registered programs, each built on first ask; a
    program whose table cannot be built is left out."""
    with _LIVE_LOCK:
        todo = {n: b for n, b in _PROGRAM_SOURCES.items()
                if n not in _PROGRAM_TABLES}
    for name, build in todo.items():
        table = build()
        if table is not None:
            with _LIVE_LOCK:
                _PROGRAM_TABLES[name] = table
    with _LIVE_LOCK:
        return dict(_PROGRAM_TABLES)


class LoopProfiler:
    """Per-launch accounting for the engine loop.

    ``clock`` is injectable (the GoodputAccounter pattern) so tests
    script exact phase durations.  All mutation happens on the engine
    loop thread; ``stats()`` and the ring are read from HTTP handler
    threads, so the cumulative counters and the rings live under
    ``_lock``.
    """

    # lint-enforced (graft-race TH001): the rollup counters are written
    # by the engine loop (finish) and read by /metrics handler threads
    # (stats), so every access goes through _lock.  _last_end, _seq,
    # _gap_note, stall_armed and _wait_medians are engine-loop/warmup-
    # thread only (single writer, never read across roots).  _open,
    # _thread, _gap_compile, _gap_gc and _credited are written by the
    # loop and by the two callbacks above, which take no lock on
    # purpose: under the GIL a credit is lost at worst.
    _lock_protected_ = {
        "dispatches": "_lock",
        "dispatches_by_kind": "_lock",
        "wall_secs": "_lock",
        "gap_secs": "_lock",
        "phase_secs": "_lock",
        "_phase_counts": "_lock",
        "_phase_launches": "_lock",
        "stalls": "_lock",
        "_totals": "_lock",
        "_ring": "_lock",
        "_requests": "_lock",
        "_emitted_at_dispatches": "_lock",
        "_emitted_at_time": "_lock",
    }

    def __init__(self, ring_size: int = RING_SIZE,
                 stall_threshold_secs: float = 0.5,
                 emit_every_dispatches: int = 256,
                 emit_interval_secs: float = 15.0,
                 clock=time.perf_counter):
        self._clock = clock
        self.stall_threshold_secs = float(stall_threshold_secs)
        self.emit_every_dispatches = int(emit_every_dispatches)
        self.emit_interval_secs = float(emit_interval_secs)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(int(ring_size), 1))
        self._requests: deque = deque(maxlen=REQUEST_RING_SIZE)
        self.dispatches = 0
        self.dispatches_by_kind = {"prefill": 0, "decode": 0, "verify": 0}
        self.wall_secs = 0.0        # sum of launch wall-clocks
        self.gap_secs = 0.0         # between consecutive busy launches
        self.phase_secs = {p: 0.0 for p in LOOP_PHASES}
        # the phase histograms' raw counts (a phase that took no time in
        # a launch is not in them), kept under _lock with the rest and
        # given the mergeable snapshot shape in stats()
        self._phase_counts = [[0] * (len(LOOP_PHASE_BUCKETS) + 1)
                              for _ in LOOP_PHASES]
        self._phase_launches = [0] * len(LOOP_PHASES)
        self.stalls = 0
        # every counted field of the launches that finished, summed
        self._totals = dict.fromkeys(COUNTED_FIELDS, 0)
        # armed by the engine after warmup(): compile-time gaps between
        # warmup dispatches are expected, not stalls
        self.stall_armed = False
        self._last_end: Optional[float] = None
        self._seq = 0
        self._gap_note: Optional[TraceAnnotation] = None
        # the open launch and the thread its loop runs on, for the two
        # callbacks; what they credited while none was open (the gap's
        # account, handed to the next launch); and the ledger's intervals
        # credited since the last begin or finish, newest last (an inner
        # trace ends before the one it lies in: a union, not a sum)
        self._open: Optional[DispatchRecord] = None
        self._thread = 0
        self._gap_compile = 0.0
        self._gap_gc = 0.0
        self._credited: List[Tuple[float, float]] = []
        # the stall detector's running median of dispatch + fetch by
        # kind (None: too few launches of the kind in the window)
        self._wait_medians: Dict[str, Optional[float]] = {}
        self._emitted_at_dispatches = 0
        self._emitted_at_time = self._clock()
        # the programs' instruction tables by program name, and what
        # builds them (the engine's program_tables): see program_tables()
        self.programs: Dict[str, Any] = {}
        self.program_source: Optional[Callable[[], Dict[str, Any]]] = None
        with _LIVE_LOCK:
            _LIVE.append(self)
        _watch(self)

    # -- per-launch protocol (engine loop thread only) ------------------

    def begin(self) -> DispatchRecord:
        """Open a launch record; the gap since the previous launch's
        finish is the loop's dead time (zero when ``idle()`` broke the
        chain — an empty engine is not a stall)."""
        now = self._clock()
        note = self._gap_note
        if note is not None:
            self._gap_note = None
            note.__exit__(None, None, None)
        last = self._last_end
        gap = max(now - last, 0.0) if last is not None else 0.0
        d = DispatchRecord(self._clock, self._seq, now, gap)
        if self._gap_compile or self._gap_gc:
            d.gap_compile_secs, d.gap_gc_secs = (self._gap_compile,
                                                 self._gap_gc)
            self._gap_compile = self._gap_gc = 0.0
            del self._credited[:]
        self._thread = threading.get_ident()
        self._open = d
        return d

    def idle(self, d: Optional[DispatchRecord] = None) -> None:
        """The scheduler had no action: ``d`` is no launch, and the
        chain breaks so the wait for new work never reads as a gap."""
        if d is not None:
            d._note.__exit__(None, None, None)
        self._last_end = None
        self._open = None
        self._gap_compile = self._gap_gc = 0.0
        del self._credited[:]

    def _credit_compile(self, start: float, end: float) -> None:
        """An event of the compile ledger ended on the loop's thread:
        its seconds, less what was already credited inside it, go to the
        open launch, or to the gap's account."""
        secs = end - start
        credited = self._credited
        while credited and credited[-1][1] > start:
            s, e = credited.pop()
            secs -= e - s
        credited.append((start, end))
        d = self._open
        if d is not None:
            d.compile_secs += secs
        else:
            self._gap_compile += secs

    def _take_medians(self, recent: List[DispatchRecord]) -> None:
        by_kind: Dict[str, List[float]] = {}
        for r in recent:
            by_kind.setdefault(r.kind, []).append(
                r.t[_FETCH + 1] - r.t[_DISPATCH])
        self._wait_medians = {
            k: median(v) if len(v) >= _MEDIAN_MIN_LAUNCHES else None
            for k, v in by_kind.items()}

    def finish(self, d: DispatchRecord) -> None:
        """Close the record: the tail since the last mark goes to
        ``emit``, the record enters the ring, rollups update, and the
        stall / tracer / periodic-emission side effects fire.  Never
        raises — the engine loop must survive any telemetry trouble."""
        now = self._clock()
        d._close(now)
        self._open = None
        if d.compile_secs:
            del self._credited[:]
        t = d.t
        # a stall: the gap before the launch over the threshold, or its
        # own dispatch + fetch over SLOW_FACTOR times its kind's running
        # median (slow_over) and SLOW_MIN_SECS
        stalled, slow_over, recent = False, None, None
        if self.stall_armed:
            stalled = d.gap_secs > self.stall_threshold_secs
            wait = t[_FETCH + 1] - t[_DISPATCH]
            if wait > SLOW_MIN_SECS:
                med = self._wait_medians.get(d.kind)
                if med is not None and wait > SLOW_FACTOR * med:
                    stalled, slow_over = True, med
        with self._lock:
            self.dispatches += 1
            n = self.dispatches
            self.dispatches_by_kind[d.kind] = (
                self.dispatches_by_kind.get(d.kind, 0) + 1)
            self.wall_secs += t[-1] - t[0]
            self.gap_secs += d.gap_secs
            secs = self.phase_secs
            for i, p in enumerate(LOOP_PHASES):
                v = t[i + 1] - t[i]
                if v > 0.0:
                    secs[p] += v
                    self._phase_counts[i][
                        bisect_left(LOOP_PHASE_BUCKETS, v)] += 1
                    self._phase_launches[i] += 1
            if stalled:
                self.stalls += 1
            totals = self._totals
            for f in COUNTED_FIELDS:
                totals[f] += getattr(d, f)
            self._ring.append(d)
            if n % (_MEDIANS_EVERY if n > WINDOW_DISPATCHES
                    else _MEDIAN_MIN_LAUNCHES) == 0:
                recent = list(islice(reversed(self._ring),
                                     WINDOW_DISPATCHES))
        if recent is not None:
            self._take_medians(recent)
        self._seq = d.seq + 1
        self._last_end = now
        self._gap_note = TraceAnnotation("loop.gap", seq=self._seq)
        self._gap_note.__enter__()
        if stalled:
            try:
                fr = telemetry.get_flight_recorder()
                if fr is not None:
                    fr.record({"kind": "loop_stall",
                               "time_unix": time.time(),
                               "gap_secs": round(d.gap_secs, 6),
                               "threshold_secs": self.stall_threshold_secs,
                               "dispatch": n,
                               "dispatch_kind": d.kind,
                               "seq": d.seq,
                               "wait_secs": round(d.wait_secs, 6),
                               "wait_median_secs": slow_over,
                               "compile_secs": round(d.compile_secs, 6),
                               "gc_secs": round(d.gc_secs, 6)})
            except Exception:   # noqa: BLE001 - diagnostics never kill
                pass
        tracer = tracing.get_tracer()
        if tracer is not None:
            try:
                self._export(tracer, d)
            except Exception:   # noqa: BLE001
                pass
        self.maybe_emit(now=now)

    @staticmethod
    def _export(tracer, d: DispatchRecord) -> None:
        """The record as Chrome-trace spans: the sub-spans that tile it,
        and the enclosing launch span the lifecycle report joins on."""
        t = d.t
        for i, p in enumerate(LOOP_PHASES):
            if t[i + 1] > t[i]:
                tracer.completed(f"loop.{p}", "serve_loop", start=t[i],
                                 dur_secs=t[i + 1] - t[i], kind=d.kind,
                                 seq=d.seq)
        if d.kind == "prefill":
            attrs = {"request": d.request,
                     "trace": d.traces[0] if d.traces else None,
                     "tokens": d.valid, "cached_tokens": d.cached_tokens}
        else:
            attrs = {"batch": d.rows, "traces": list(d.traces)}
            if d.kind == "verify":
                attrs["drafted"] = d.drafted
        tracer.completed(_SPAN_NAME[d.kind], "serve", start=t[_DISPATCH],
                         dur_secs=d.wait_secs, seq=d.seq, **attrs)

    def record_request(self, span: RequestSpan) -> None:
        """A retired request's own span, kept beside the launches
        whether or not a SpanTracer is installed."""
        with self._lock:
            self._requests.append(span)

    # -- reading --------------------------------------------------------

    def launches(self) -> int:
        with self._lock:
            return self.dispatches

    def records(self, last: Optional[int] = None) -> List[DispatchRecord]:
        """The ring, oldest first (its last ``last`` launches): the
        records themselves, read-only."""
        with self._lock:
            if last is None or last >= len(self._ring):
                return list(self._ring)
            return list(islice(reversed(self._ring), last))[::-1]

    def totals(self) -> Dict[str, float]:
        """Every counted field (``COUNTED_FIELDS``) summed over the
        launches that finished: what the engine's ``stats()`` reports."""
        with self._lock:
            return dict(self._totals)

    def program_tables(self) -> Dict[str, Any]:
        """``programs``, built first if nobody has asked yet (the
        engine's ``program_tables``: a reader's cost, after the launches
        it wants to explain)."""
        if not self.programs and self.program_source is not None:
            self.programs = self.program_source()
        return self.programs

    def request_spans(self) -> List[RequestSpan]:
        with self._lock:
            return list(self._requests)

    def ring_records(self, last: int = WINDOW_DISPATCHES
                     ) -> List[Dict[str, Any]]:
        """JSON-able copy of the newest launches — the raw material
        postmortem bundles freeze when an alert fires
        (serving/alerts.py)."""
        return [d.as_dict() for d in self.records(last)]

    @staticmethod
    def _wait_pcts(wait: float, wall: float, gap: float):
        """(wait_pct, host_bubble_pct) over a busy window of
        ``wall + gap`` seconds; (None, None) on an empty window."""
        busy = wall + gap
        if busy <= 0.0:
            return None, None
        pct = 100.0 * min(wait / busy, 1.0)
        return round(pct, 3), round(100.0 - pct, 3)

    def stats(self) -> Dict[str, Any]:
        """JSON-able rollup for the engine's ``/metrics`` block.  The
        phase histograms carry the mergeable ``Histogram.snapshot()``
        shape, so the Prometheus exposition renders them as real
        histogram series and the router's fleet merge bucket-sums
        them."""
        with self._lock:
            dispatches = self.dispatches
            by_kind = dict(self.dispatches_by_kind)
            wall = self.wall_secs
            gap = self.gap_secs
            phase_secs = dict(self.phase_secs)
            stalls = self.stalls
            moved = {f: self._totals[f] for f in HOST_FIELDS}
            counts = [list(c) for c in self._phase_counts]
            timed = list(self._phase_launches)
        snaps = {p: telemetry.histogram_snapshot(
                     LOOP_PHASE_BUCKETS, counts[i], timed[i], phase_secs[p])
                 for i, p in enumerate(LOOP_PHASES)}
        recent = self.records(WINDOW_DISPATCHES)
        wait = phase_secs["dispatch"] + phase_secs["fetch"]
        wait_pct, bubble_pct = self._wait_pcts(wait, wall, gap)
        w_wall = sum(r.wall_secs for r in recent)
        w_gap = sum(r.gap_secs for r in recent)
        w_wait = sum(r.wait_secs for r in recent)
        w_wait_pct, w_bubble_pct = self._wait_pcts(w_wait, w_wall, w_gap)
        p50 = {p: telemetry.histogram_percentile(s, 0.50)
               for p, s in snaps.items()}
        p95 = {p: telemetry.histogram_percentile(s, 0.95)
               for p, s in snaps.items()}
        return {
            "dispatches": dispatches,
            "dispatches_by_kind": by_kind,
            "wall_secs": round(wall, 6),
            "gap_secs": round(gap, 6),
            "wait_secs": round(wait, 6),
            "phase_secs": {p: round(v, 6) for p, v in phase_secs.items()},
            "wait_pct": wait_pct,
            "host_bubble_pct": bubble_pct,
            "stalls": stalls,
            **moved,
            "window": {
                "dispatches": len(recent),
                "wall_secs": round(w_wall, 6),
                "wait_pct": w_wait_pct,
                "host_bubble_pct": w_bubble_pct,
            },
            "phase_p50_secs": p50,
            "phase_p95_secs": p95,
            "histograms": {f"loop_{p}_secs": s for p, s in snaps.items()},
        }

    def loop_stats_record(self) -> Dict[str, Any]:
        """The periodic ``engine_loop_stats`` JSONL record: the
        ``stats()`` rollup minus the bulky histogram snapshots —
        scalar p50/p95 travel instead."""
        s = self.stats()
        s.pop("histograms", None)
        return {"kind": "serve", "event": "engine_loop_stats", **s}

    def maybe_emit(self, now: Optional[float] = None,
                   force: bool = False) -> bool:
        """Emit ``engine_loop_stats`` to the telemetry stream when due
        (every ``emit_every_dispatches`` dispatches or
        ``emit_interval_secs`` seconds with at least one new dispatch),
        or unconditionally with ``force``.  True when a record was
        written."""
        stream = telemetry.get_stream()
        if stream is None:
            return False
        if now is None:
            now = self._clock()
        with self._lock:
            fresh = self.dispatches - self._emitted_at_dispatches
            due = force or fresh >= self.emit_every_dispatches or (
                fresh > 0
                and now - self._emitted_at_time >= self.emit_interval_secs)
            if not due:
                return False
            self._emitted_at_dispatches = self.dispatches
            self._emitted_at_time = now
        try:
            stream.emit(self.loop_stats_record())
        except Exception:       # noqa: BLE001 - engine loop must survive
            return False
        return True
