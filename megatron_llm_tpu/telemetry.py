"""Unified runtime telemetry: throughput/MFU stream, structured JSONL log,
flight recorder, in-loop profiler capture.

Motivation (MegaScale, arXiv:2402.15627 §5): at scale, "is the run
healthy and fast?" must be answerable from the run itself — per-step
telemetry in a structured stream, in-situ profiler capture, and a flight
recorder consulted on failure.  The reference Megatron-LM computes a
throughput estimate inside ``training_log`` (arXiv:2104.04473;
training.py:591-609) but has no machine-readable stream and no profiler
integration.  This module puts that layer *in* the training loop (the
benchmark under ``benchmarks/`` measures from outside, with its own
peak table):

* **ThroughputCalculator** — tokens/sec, tokens/sec/device, achieved
  TFLOPs/device and MFU from the model-level ``flops_per_token()`` and
  the per-chip peak-FLOPs table ``PEAK_FLOPS``.  MFU carries the
  > ``MFU_SANITY_LIMIT`` fabrication guard: a physically impossible
  number means the timing failed to sync with the device, and is
  reported as null, never as a value.

* **TelemetryStream** (``--structured_log_dir``) — one JSONL record per
  log boundary: iteration, losses, grad_norm, lr, step time, throughput
  / MFU, per-device ``memory_stats()``, recovery counters.  Records are
  versioned (``schema``) and written line-buffered by process 0 only.

* **FlightRecorder** — bounded in-memory deque of the last K step
  records (lightweight per-iteration dispatch entries + the full
  log-boundary records).  The resilience watchdog/crash path dumps it
  next to its thread-stack report (``resilience.dump_stacks_and_memory``)
  and, when a structured log dir exists, as ``flight_recorder.json``
  beside the stream — MegaScale's "what were the last things the run
  did" forensics.

* **ProfilerSession** (``--profile --profile_step_start N
  --profile_step_end M --profile_dir D``) — wraps the chosen step window
  in ``jax.profiler`` trace capture during real training;
  ``--profiler_port`` starts
  ``jax.profiler.start_server`` for live TensorBoard capture.
  ``jax.named_scope`` annotations on the embedding / transformer layers
  / pipeline stages make the resulting xplane legible.

Everything here is host-side: nothing enters the jitted step, so
telemetry costs nothing on the XLA program.  Collective discipline
matches ``dist_signal_handler.py``: any cross-host reduction happens
only at deterministic log boundaries (see ``timers.Timers``).
"""

from __future__ import annotations

import bisect
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax

from megatron_llm_tpu.global_vars import get_counters

# ---------------------------------------------------------------------------
# Peak FLOPs / MFU
# ---------------------------------------------------------------------------

# bf16 peak per chip (Google Cloud TPU documentation, the page of each
# generation), keyed by device_kind substrings; spellings vary across
# libtpu versions (v5e reports "TPU v5 lite" or "TPU v5e").
# This is the PROGRAM's table: the training log's MFU and its > 0.95
# guard read it.  The benchmark keeps its own
# (benchmarks/harness/peaks.json), so that no PR to the program can move
# a number the benchmark reports.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# MFU above this is physically impossible — the timing loop failed to
# sync with the device (a 1380-MFU "measurement" was caught this way).
# The runtime stream reports null instead.
MFU_SANITY_LIMIT = 0.95


def peak_flops_for_kind(device_kind: str) -> float:
    """Peak bf16 FLOP/s for a ``device_kind`` string.  A kind the table
    does not hold is an error, not a default: an MFU over a guessed peak
    is a made-up number."""
    for k, v in PEAK_FLOPS.items():
        if k in device_kind:
            return v
    raise ValueError(
        f"no peak FLOP/s on record for device kind {device_kind!r}: add "
        f"it to telemetry.PEAK_FLOPS with its source before asking for "
        f"an MFU on it")


def peak_flops_for_local_device() -> Optional[float]:
    """Peak FLOP/s of this host's devices (they are of one kind).  None
    on the CPU backend, where no MFU is asked for; an accelerator the
    table does not know raises (``peak_flops_for_kind``)."""
    if jax.default_backend() == "cpu":
        return None
    return peak_flops_for_kind(jax.local_devices()[0].device_kind)


class ThroughputCalculator:
    """Tokens/sec(/device), achieved TFLOPs/device and MFU from wall time.

    ``flops_per_token`` is the model-level fwd+bwd estimate
    (``model.flops_per_token()``, models/language_model.py); ``peak_flops``
    the per-chip bf16 peak (None => MFU is always null).  All host-side
    float arithmetic — free at log boundaries."""

    def __init__(self, flops_per_token: Optional[float] = None,
                 device_count: Optional[int] = None,
                 peak_flops: Optional[float] = None):
        self.flops_per_token = flops_per_token
        self._device_count = device_count
        self.peak_flops = peak_flops

    @classmethod
    def from_model(cls, model, device_count: Optional[int] = None,
                   peak_flops: Optional[float] = "auto"):
        """Build from any model exposing ``flops_per_token()`` (models
        without one still get tokens/sec accounting)."""
        fpt = None
        fn = getattr(model, "flops_per_token", None)
        if callable(fn):
            try:
                fpt = float(fn())
            except Exception:
                fpt = None
        if peak_flops == "auto":
            peak_flops = peak_flops_for_local_device()
        return cls(flops_per_token=fpt, device_count=device_count,
                   peak_flops=peak_flops)

    @property
    def device_count(self) -> int:
        if self._device_count is None:
            self._device_count = jax.device_count()
        return self._device_count

    def compute(self, tokens: float, elapsed_secs: float) -> Dict[str, Any]:
        """One log boundary's throughput record.  ``tokens`` is the global
        token count per iteration, ``elapsed_secs`` the per-iteration wall
        time.  MFU is null when the peak is unknown (CPU) or the number
        trips the fabrication guard — never a made-up value."""
        n = max(self.device_count, 1)
        tps = tokens / max(elapsed_secs, 1e-9)
        out: Dict[str, Any] = {
            "tokens_per_sec": tps,
            "tokens_per_sec_per_device": tps / n,
            "tflops_per_device": None,
            "mfu": None,
        }
        if self.flops_per_token:
            achieved = tps * self.flops_per_token / n
            out["tflops_per_device"] = achieved / 1e12
            if self.peak_flops:
                mfu = achieved / self.peak_flops
                out["mfu"] = mfu if mfu <= MFU_SANITY_LIMIT else None
        return out


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Bounded deque of the last K step records (MegaScale §5.3: the
    record consulted when a run dies).  Two record kinds: ``dispatch``
    (per-iteration, host-only — never syncs the device) and ``log`` (the
    full log-boundary record)."""

    def __init__(self, capacity: int = 64):
        self.capacity = int(capacity)
        self._records: deque = deque(maxlen=max(self.capacity, 1))

    def record(self, rec: Dict[str, Any]) -> None:
        self._records.append(rec)

    def records(self) -> List[Dict[str, Any]]:
        return list(self._records)

    def __len__(self) -> int:
        return len(self._records)

    def dump(self, path: str, reason: str = "") -> str:
        """Write the recorder as JSON (atomic: tmp + rename — the caller
        may be a watchdog thread racing process death)."""
        payload = {
            "dumped_at_unix": time.time(),
            "reason": reason,
            "capacity": self.capacity,
            "records": self.records(),
        }
        return atomic_write_json(path, payload)


# ---------------------------------------------------------------------------
# Atomic snapshot writing (shared by the flight recorder, the crash
# path's stack dump, and the serving alert engine's postmortem bundles)
# ---------------------------------------------------------------------------

def atomic_write_json(path: str, payload: Any, indent: int = 1) -> str:
    """Write JSON atomically (tmp + rename): a reader — or a scraper
    racing process death — sees either the old file or the complete new
    one, never a truncated write."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=indent, default=str)
    os.replace(tmp, path)
    return path


def capture_thread_stacks() -> str:
    """All-thread stack report (the watchdog/crash dump and the alert
    bundles share this): one block per thread with name/daemon flag and
    the formatted frames from ``sys._current_frames``."""
    import sys
    import traceback

    frames = sys._current_frames()
    names = {t.ident: t for t in threading.enumerate()}
    out = []
    for ident, frame in sorted(frames.items()):
        t = names.get(ident)
        label = f"{t.name}{' (daemon)' if t.daemon else ''}" \
            if t is not None else "unknown"
        out.append(f"--- thread {label} (ident {ident}) ---")
        out.append("".join(traceback.format_stack(frame)))
    return "\n".join(out)


def write_snapshot_bundle(dir_path: str, parts: Dict[str, Any],
                          max_bytes_per_part: int = 2_000_000,
                          manifest_extra: Optional[Dict[str, Any]] = None
                          ) -> str:
    """Write a postmortem bundle as an atomically-published directory.

    ``parts`` maps part name -> payload: a str becomes ``<name>.txt``,
    anything else JSON-serializes to ``<name>.json``.  Every part is
    size-bounded (oversize payloads are truncated with a marker, never
    dropped silently) and the bundle carries a ``manifest.json`` listing
    what landed.  The whole directory is staged under a pid-suffixed tmp
    name and published with one ``os.replace`` so a reader never sees a
    half-written bundle — the same tmp+rename discipline as
    :func:`atomic_write_json`, at directory granularity."""
    tmp = f"{dir_path}.tmp.{os.getpid()}"
    os.makedirs(tmp, exist_ok=True)
    manifest: Dict[str, Any] = {
        "written_at_unix": time.time(),
        "parts": {},
    }
    if manifest_extra:
        manifest.update(manifest_extra)
    for name, payload in sorted(parts.items()):
        try:
            if isinstance(payload, str):
                fname, data = f"{name}.txt", payload
            else:
                fname, data = f"{name}.json", json.dumps(
                    payload, indent=1, default=str)
            truncated = False
            if len(data) > max_bytes_per_part:
                data = data[:max_bytes_per_part] \
                    + "\n...[truncated by bundle size bound]"
                truncated = True
            with open(os.path.join(tmp, fname), "w") as f:
                f.write(data)
            manifest["parts"][name] = {"file": fname,
                                       "bytes": len(data),
                                       "truncated": truncated}
        except Exception as exc:    # noqa: BLE001 - forensics: best effort
            manifest["parts"][name] = {"error": repr(exc)}
    atomic_write_json(os.path.join(tmp, "manifest.json"), manifest)
    if os.path.isdir(dir_path):     # an older bundle with the same name
        os.replace(os.path.join(tmp, "manifest.json"),
                   os.path.join(dir_path, "manifest.json"))
        for f in os.listdir(tmp):
            os.replace(os.path.join(tmp, f), os.path.join(dir_path, f))
        os.rmdir(tmp)
    else:
        os.replace(tmp, dir_path)
    return dir_path


# ---------------------------------------------------------------------------
# Device memory
# ---------------------------------------------------------------------------

def device_memory_stats(device=None) -> Dict[str, int]:
    """``memory_stats()`` reduced to the portable keys (bytes_in_use,
    peak_bytes_in_use, largest_alloc_size, num_allocs — whichever the
    backend reports).  For one ``device``, or by default the largest
    value of each key over this host's devices: the fullest device is
    the one that runs out, and under a sharded layout that need not be
    device 0.  {} when unavailable (CPU backends often return None)."""
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size", "num_allocs")
    devices = jax.local_devices() if device is None else [device]
    out: Dict[str, int] = {}
    for d in devices:
        stats = d.memory_stats() or {}
        for k in keep:
            if k in stats:
                out[k] = max(out.get(k, 0), int(stats[k]))
    return out


# ---------------------------------------------------------------------------
# Fixed-bucket histograms (SLO accounting)
# ---------------------------------------------------------------------------

# Prometheus-style latency buckets (seconds).  Fixed across the fleet so
# replica histograms merge by bucket-sum in the router's /metrics.
DEFAULT_LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)

_INF_LABEL = "+Inf"


def _bucket_label(bound: float) -> str:
    return format(bound, "g")


class Histogram:
    """Stdlib fixed-bucket histogram, mergeable by bucket-sum.

    Snapshots carry per-bucket (non-cumulative) counts keyed by the
    bucket's upper bound, plus ``count`` and ``sum`` — all additive, so
    the router's recursive numeric sum over replica snapshots IS the
    fleet histogram.  Percentiles come from ``histogram_percentile``
    (linear interpolation inside the winning bucket), computed at read
    time and never stored, so they can't be accidentally summed."""

    def __init__(self, bounds=DEFAULT_LATENCY_BUCKETS):
        self.bounds = tuple(sorted(float(b) for b in bounds))
        if not self.bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self._counts = [0] * (len(self.bounds) + 1)     # last = +Inf
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value) -> None:
        if value is None:
            return
        v = float(value)
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    @property
    def count(self) -> int:
        return self._count

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            counts = list(self._counts)
            total, s = self._count, self._sum
        return histogram_snapshot(self.bounds, counts, total, s)


def histogram_snapshot(bounds, counts, total: int, s: float
                       ) -> Dict[str, Any]:
    """The mergeable snapshot shape from raw per-bucket counts
    (``len(bounds) + 1`` of them, the last the +Inf bucket) — for a
    writer that keeps its counts under a lock of its own."""
    buckets = {_bucket_label(b): counts[i] for i, b in enumerate(bounds)}
    buckets[_INF_LABEL] = counts[-1]
    return {"buckets": buckets, "count": total, "sum": round(s, 9)}


def is_histogram_snapshot(d: Any) -> bool:
    """Structural check shared by the Prometheus renderer and the router
    aggregation: a dict with a str->number ``buckets`` dict plus
    ``count``/``sum`` leaves."""
    return (isinstance(d, dict) and "count" in d and "sum" in d
            and isinstance(d.get("buckets"), dict))


def histogram_percentile(snap: Dict[str, Any], q: float) -> Optional[float]:
    """Estimate the q-quantile from a (possibly merged) histogram
    snapshot.  Linear interpolation within the winning bucket; the +Inf
    bucket answers with its lower edge (the largest finite bound) — an
    under-estimate, never an invention.  None on an empty histogram."""
    if not is_histogram_snapshot(snap):
        return None
    total = snap.get("count") or 0
    if total <= 0:
        return None
    items = []
    for k, v in snap["buckets"].items():
        bound = float("inf") if k in (_INF_LABEL, "inf") else float(k)
        items.append((bound, int(v)))
    items.sort()
    target = max(min(float(q), 1.0), 0.0) * total
    cum = 0
    lo = 0.0
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


# ---------------------------------------------------------------------------
# Prometheus text exposition (shared by serving /metrics, the router's
# fleet /metrics, and the trainer's --status_port endpoint)
# ---------------------------------------------------------------------------

def _metric_name(name: str) -> str:
    name = "".join(c if (c.isalnum() and c.isascii()) or c == "_"
                   else "_" for c in name)
    if name and name[0].isdigit():
        name = "_" + name
    return name


def prometheus_exposition(snapshot: dict,
                          prefix: str = "megatron_serve_") -> str:
    """Render a metrics snapshot dict as Prometheus text exposition
    format (0.0.4) so standard scrapers can hit ``/metrics`` without a
    JSON-translating sidecar.  Nested dicts (the ``engine`` block, its
    per-reason completion counts) flatten into underscore-joined names;
    None values (e.g. empty-window percentiles) are omitted; numbers are
    exported as gauges — the scraper cannot tell a monotone counter from
    a level, and gauge is always safe.  Histogram snapshots (the
    ``Histogram.snapshot()`` shape) render as proper Prometheus
    histograms: cumulative ``_bucket{le=...}`` series plus ``_sum`` and
    ``_count``.  An ``alerts`` block (the serving alert engine's
    snapshot shape) renders its firing list as the labeled gauge
    ``megatron_alert_firing{rule=...,scope=...} 1`` — the one labeled
    series in the exposition, with a fixed unprefixed name so the same
    alerting config scrapes replica and fleet endpoints alike — and its
    numeric counters as ordinary gauges."""
    lines = []

    def esc(v):
        return str(v).replace("\\", "\\\\").replace('"', '\\"')

    def emit_alert_block(path, block):
        lines.append("# TYPE megatron_alert_firing gauge")
        for entry in block.get("firing") or []:
            if not isinstance(entry, dict):
                continue
            lines.append(
                f'megatron_alert_firing{{rule="{esc(entry.get("rule"))}"'
                f',scope="{esc(entry.get("scope"))}"'
                f',severity="{esc(entry.get("severity"))}"}} 1')
        rest = {k: v for k, v in block.items()
                if k not in ("firing", "pending")}
        walk(rest, path)

    def emit(name, value):
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            return
        name = _metric_name(name)
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {float(value):g}")

    def emit_histogram(name, snap):
        name = _metric_name(name)
        items = []
        for k, v in snap["buckets"].items():
            bound = float("inf") if k in (_INF_LABEL, "inf") else float(k)
            items.append((bound, k, int(v)))
        items.sort()
        lines.append(f"# TYPE {name} histogram")
        cum = 0
        for bound, label, c in items:
            cum += c
            lines.append(f'{name}_bucket{{le="{label}"}} {cum}')
        lines.append(f"{name}_sum {float(snap.get('sum') or 0.0):g}")
        lines.append(f"{name}_count {int(snap.get('count') or 0)}")

    def walk(d, path):
        for k, v in sorted(d.items()):
            if is_histogram_snapshot(v):
                emit_histogram(f"{path}{k}", v)
            elif k == "alerts" and isinstance(v, dict) \
                    and isinstance(v.get("firing"), list):
                emit_alert_block(f"{path}{k}_", v)
            elif isinstance(v, dict):
                walk(v, f"{path}{k}_")
            else:
                emit(f"{path}{k}", v)

    walk(snapshot, prefix)
    return "\n".join(lines) + "\n"


def _wants_prometheus(path: str, accept: str) -> bool:
    """Content negotiation for /metrics: an explicit ?format=prometheus
    query wins; otherwise an Accept header preferring text/plain (what
    the Prometheus scraper sends) selects the text exposition."""
    query = path.partition("?")[2]
    for pair in query.split("&"):
        if pair.partition("=")[::2] == ("format", "prometheus"):
            return True
    accept = accept.lower()
    return ("text/plain" in accept or "openmetrics" in accept) \
        and "application/json" not in accept


# ---------------------------------------------------------------------------
# Structured JSONL stream
# ---------------------------------------------------------------------------

# 2: + interval_time_secs / goodput / tracing
# 3: + layer_stats (per-group grad/param/update norms, non-finite counts —
#    see health.py) on records at --log_layer_stats_interval boundaries
# 4: + per-slice attribution on multi-slice runs (slice_times /
#    worst_slice / goodput.slice_stall_secs) and the elastic_resume /
#    preempt_rescue event kinds — see multislice.py
# 5: serve request_done records gain trace_id (the router-minted
#    X-Request-Trace id), per-request phase attribution (phases.queue_secs
#    / admission_secs / prefill_secs / decode_secs / stream_write_secs),
#    tpot_secs (amortized per-output-token decode latency), decode_tokens
#    and prefill_computed_tokens — see serving/engine.py and
#    tools/serve_report.py
# 6: serve request_done records gain prefill_kernel (the resolved
#    chunked-prefill attention path, 'pallas'|'xla', alongside the
#    existing decode-path paged_kernel) — see serving/engine.py
# 7: + kind="fleet" supervisor events (replica_spawned / replica_died /
#    replica_respawned / scale_up / scale_down / brownout, each with
#    slot/url/reason fields) — see serving/supervisor.py and
#    tools/serve_report.py's fleet-event timeline
# 8: serve request_done records gain speculative-decoding attribution:
#    drafted_tokens / accepted_tokens (prompt-lookup proposals this
#    request rode into verify steps and the subset verification
#    committed) and accept_rate (accepted/drafted, null when the request
#    never drafted) — see serving/engine.py and serving/drafter.py
# 9: + router-tier fleet events (router_spawned / router_died /
#    router_respawned / router_scale_up / router_scale_down, with
#    slot/url and the dispatch-p95/in-flight readings behind scaling
#    decisions) — see serving/supervisor.py's sharded front door
# 10: + kind="serve" event="engine_loop_stats" records (periodic
#    engine-loop goodput rollups: per-phase seconds, host_bubble_pct,
#    dispatch-gap stall count, windowed recents and phase p50/p95) —
#    see serving/loop_profiler.py and tools/serve_report.py's
#    loop-goodput section
# 11: + kind="serve" event="cache_stats" records (periodic KV
#    prefix-cache observatory rollups: salted-digest heat top-K,
#    miss-cause taxonomy cold/evicted, capacity-vs-churn eviction
#    forensics, ghost-tier hit projections at 2x/4x/10x capacity);
#    request_done records gain miss_cold_blocks / miss_evicted_blocks
#    (per-request prefix miss causes; evicted = the evicted-then-
#    wanted-again regret signal) — see serving/cache_observatory.py
#    and tools/serve_report.py's cache-observatory section
# 12: hierarchical KV cache (host-RAM spill tier under the HBM pool;
#    serving/host_cache.py): request_done records gain host_hit_blocks
#    (prefix blocks rescued from the host tier) and swap_in_secs (the
#    host→device scatter time the request paid for them); cache_stats
#    records gain host_hits / host_hit_tokens / swap_in_blocks and a
#    "host" sub-block (spill/eviction/swap-in counters, budget usage)
# 13: + alert_transition events (serving/alerts.py SLO sentinel):
#    kind="serve" per-replica (and kind="fleet" at the supervisor's
#    merged scope) records with rule / scope / state
#    (pending|firing|resolved) / severity / value / threshold /
#    window_secs / since_unix / bundle (the postmortem bundle directory
#    captured on firing) — see serving/alerts.py and
#    tools/serve_report.py's incident timeline
# 14: engine_loop_stats says what its clocks are: the phase ``device``
#    (a host clock around dispatch and three fetches) is split into
#    ``dispatch`` (until the jitted call returns) and ``fetch`` (until
#    the last blocking read returns); device_secs / device_busy_pct
#    become wait_secs / wait_pct (their sum, what the host waited);
#    host_bubble_pct = 100 - wait_pct keeps its name and meaning
# 15: a sparse model's routing: engine stats() / the engine block of
#    /metrics gain moe_assignments, moe_experts_touched, moe_expert_slots
#    and moe_busiest_expert_assignments (live token x choice assignments,
#    experts with at least one, layers x experts, each layer's largest
#    count; summed over launches, all 0 for a dense model), and every
#    launch record of the loop profiler's ring and of a postmortem
#    bundle carries the same four for that launch
# 16: the sampler's work: engine stats() / the engine block of /metrics
#    gain sample_draw_steps and sample_sort_steps (of decode_steps, the
#    steps in which a live row was not greedy, and those in which such a
#    row had an active top-k or top-p: text_generation/sampling.py
#    ``sample_batched`` draws and sorts in those steps and no others),
#    and every decode/verify launch record carries sampler_rows_drawn
#    and sampler_rows_filtered (those rows, counted)
# 17: + the train_step_program event: once a run, when the train step is
#    compiled, what its compiled text says of the data-parallel gradient
#    reduction (num_microbatches, dp, dp_grad_reductions_per_step,
#    dp_grad_reductions_in_loops, dp_grad_reduction_bytes_per_step,
#    dp_grad_reduction_dtypes) — see training.py ``_ReadStep`` and
#    hlo_collectives.py; an event is left out of the stream's step means
# 18: a sparse model's expert blocks: engine stats() / the engine block
#    of /metrics gain moe_expert_tiles, {w_in, w_out: {k, n, tk, tn,
#    steps_per_visit, vmem_bytes}}: the block the experts' grouped matmul
#    takes at each of its two matrices' widths, the grid steps a visit
#    (one group's rows in one 128-row tile) costs, and the VMEM the call
#    holds by the kernel's own count (ops/pallas/grouped_matmul.py
#    ``tiles``); static per model, absent for a dense one
# 19: the sparse-attention choice's work: engine stats() / the engine
#    block of /metrics gain dsa_select_blocks_counted and
#    dsa_select_blocks_table beside dsa_keys_live / dsa_keys_selected
#    (the blocks of keys the choice's select steps count over, each step
#    stopping at its slot's last live block, and the same had every step
#    counted its slot's whole table; summed over steps, layers and
#    launches, both 0 for a model with no indexer), and every launch
#    record carries the same two for that launch — see
#    ops/pallas/dsa_attention.py ``select_blocks``
# 20: latent attention's work: engine stats() / the engine block of
#    /metrics gain mla_keys_live (decode launches: each live row's
#    context and itself), mla_pairs (prefill launches: for each live
#    query the keys it sees) and mla_latents_expanded (prefill launches
#    whose chunk the expanded kernel reads, mla_attention_prefill: the
#    context tokens, history and chunk, it multiplies by the
#    up-projection; 0 on a decode launch and where the dense fallback
#    runs, which are absorbed), summed over
#    layers and launches, all 0 for a model without a latent pool, and
#    every launch record carries the same three for that launch — see
#    serving/loop_profiler.py ``MLA_FIELDS``
# 21: state-space layers and a share of the experts: engine stats() / the
#    engine block of /metrics gain ssm_rows_live (live rows x state-space
#    layers whose state a launch advances), ssm_tokens (tokens scanned x
#    those layers), ssm_state_bytes_held (bytes of recurrent state the
#    admitted requests hold, summed over launches as the others are) and
#    moe_assignments_held (of moe_assignments, those on an expert this
#    chip holds: equal unless moe_router_experts is set), and every launch
#    record carries the same four for that launch; blocks stats() gain
#    state_bytes_per_slot / state_bytes_held — see
#    serving/loop_profiler.py ``SSM_FIELDS``
# 22: what a launch moves between host and device: every launch record of
#    the loop profiler's ring and of a postmortem bundle gains
#    host_uploads (host arrays handed to the launch's programs, each
#    table one; the sampling arrays and the key chain live on the device
#    and count only in the launch that uploads them again after a write)
#    and host_reads (times the host waited on the launch's results, which
#    set out for the host together), and the loop block of stats() /
#    engine_loop_stats their totals under the same names — see
#    serving/loop_profiler.py ``HOST_FIELDS`` and serving/engine.py
#    ``_EngineState``
# 23: the held experts a launch touches: engine stats() / the engine block
#    of /metrics gain moe_experts_touched_held (of the experts THIS CHIP
#    HOLDS, those with at least one live assignment, summed over the
#    expert layers and launches; moe_experts_touched counts over all the
#    router scores, and the two are equal unless moe_router_experts is
#    set), and every launch record carries the same for that launch —
#    see serving/loop_profiler.py ``MOE_FIELDS``
# 24: a key changed in meaning: ``stalls`` of the loop block of stats() /
#    engine_loop_stats also counts a launch whose own dispatch + fetch
#    passed 3 times its kind's running median and 50 ms (it counted
#    gaps over the threshold only), and the flight recorder's loop_stall
#    entry says which (seq, wait_secs, wait_median_secs, compile_secs,
#    gc_secs).  Added with it, and no change by the rule below: a launch
#    record's compile_secs / gc_secs / gap_compile_secs / gap_gc_secs
#    (serving/loop_profiler.py ``HOST_FIELDS``), stats()['startup'], and
#    the ``startup`` record, one a process at "ready" (kind "startup":
#    wall_secs, spans, children, compile_secs by the ledger's kinds,
#    compile_union_secs, top_programs, cache_hits, cache_misses — see
#    tracing.py ``startup_ready``); a recompile entry carries ``program``
# The rule from here on: a key RENAMED, REMOVED or changed in meaning is a
# change of schema, and so is any change to request_done's keys (the
# lint's ratchet, analysis/telemetry_schema.py).  A counter ADDED to a
# launch record, to stats() or to engine_loop_stats is NOT (15-16 and
# 18-23 above were bumped for one by habit): readers take keys by name
# and ignore the rest, and a launch's counters have one declaration
# (serving/loop_profiler.py ``COUNTED_FIELDS``) that says what each is.
TELEMETRY_SCHEMA_VERSION = 24
STREAM_FILENAME = "telemetry.jsonl"
FLIGHT_RECORDER_FILENAME = "flight_recorder.json"


class TelemetryStream:
    """One JSONL record per log boundary under ``log_dir`` (process 0
    writes; every process keeps the flight recorder).  Tracks running
    aggregates for the end-of-run summary (mean MFU etc. — percentiles
    are the offline ``tools/telemetry_report.py``'s job)."""

    def __init__(self, log_dir: Optional[str] = None,
                 flight_recorder_size: int = 64):
        self.log_dir = log_dir
        self.flight_recorder = FlightRecorder(flight_recorder_size)
        self._file = None
        # a StatusServer (--status_port) sees every emitted record; None
        # when no live endpoint is attached
        self.status_server: Optional["StatusServer"] = None
        self._sums = {"steps": 0, "mfu": 0.0, "mfu_n": 0,
                      "tokens_per_sec_per_device": 0.0, "step_time": 0.0}
        if log_dir and jax.process_index() == 0:
            os.makedirs(log_dir, exist_ok=True)
            self._file = open(os.path.join(log_dir, STREAM_FILENAME),
                              "a", buffering=1)

    def emit(self, record: Dict[str, Any]) -> Dict[str, Any]:
        """Stamp, persist, and flight-record one log-boundary record."""
        rec = {"schema": TELEMETRY_SCHEMA_VERSION, "kind": "log",
               "time_unix": time.time(), **record}
        if self._file is not None:
            try:
                self._file.write(json.dumps(rec) + "\n")
            except ValueError:
                pass    # closed mid-shutdown while the engine retires
        if self.status_server is not None:
            self.status_server.update(rec)
        self.flight_recorder.record(rec)
        if rec["kind"] != "log":
            return rec      # an event, not a step: the means leave it out
        s = self._sums
        s["steps"] += 1
        s["step_time"] += float(rec.get("step_time_secs") or 0.0)
        s["tokens_per_sec_per_device"] += float(
            rec.get("tokens_per_sec_per_device") or 0.0)
        if rec.get("mfu") is not None:
            s["mfu"] += float(rec["mfu"])
            s["mfu_n"] += 1
        return rec

    def record_dispatch(self, rec: Dict[str, Any]) -> None:
        """Lightweight per-iteration entry — host-side fields only, never
        a device sync, so it is safe (and cheap) every step."""
        self.flight_recorder.record({"kind": "dispatch",
                                     "time_unix": time.time(), **rec})

    def summary(self) -> Dict[str, Any]:
        s = self._sums
        n = max(s["steps"], 1)
        return {
            "log_boundaries": s["steps"],
            "mean_step_time_secs": s["step_time"] / n,
            "mean_tokens_per_sec_per_device":
                s["tokens_per_sec_per_device"] / n,
            "mean_mfu": (s["mfu"] / s["mfu_n"]) if s["mfu_n"] else None,
        }

    def dump_flight_recorder(self, reason: str = "") -> Optional[str]:
        if self.log_dir is None or not len(self.flight_recorder):
            return None
        path = os.path.join(self.log_dir, FLIGHT_RECORDER_FILENAME)
        return self.flight_recorder.dump(path, reason=reason)

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None


# Active stream registry: the watchdog/crash path (resilience.py) and the
# wandb finish() summary reach the run's telemetry without threading it
# through every call chain — same pattern as resilience's save-fault hook.
_ACTIVE_STREAM: Optional[TelemetryStream] = None


def install_stream(stream: Optional[TelemetryStream]) -> None:
    global _ACTIVE_STREAM
    _ACTIVE_STREAM = stream


def get_stream() -> Optional[TelemetryStream]:
    return _ACTIVE_STREAM


def get_flight_recorder() -> Optional[FlightRecorder]:
    return _ACTIVE_STREAM.flight_recorder if _ACTIVE_STREAM else None


def dump_flight_recorder(reason: str = "") -> Optional[str]:
    """Dump the active run's flight recorder next to its JSONL stream
    (no-op without an installed stream or records).  Diagnostics path —
    never raises."""
    try:
        if _ACTIVE_STREAM is None:
            return None
        return _ACTIVE_STREAM.dump_flight_recorder(reason=reason)
    except Exception:
        return None


def run_summary() -> Optional[Dict[str, Any]]:
    """The active stream's aggregate summary (wandb finish() pulls this),
    merged with the active tracer's goodput breakdown + recompile /
    straggler counts when tracing is on."""
    out = _ACTIVE_STREAM.summary() if _ACTIVE_STREAM else None
    from megatron_llm_tpu import tracing

    g = tracing.goodput_summary()
    if g is not None:
        out = dict(out or {})
        out["goodput_pct"] = g["goodput_pct"]
        out["goodput"] = g
        out["recompiles"] = int(get_counters().get("recompiles", 0))
        out["straggler_events"] = int(
            get_counters().get("straggler_events", 0))
    return out


# ---------------------------------------------------------------------------
# In-loop profiler capture
# ---------------------------------------------------------------------------

class ProfilerSession:
    """Wraps a chosen iteration window ``[step_start, step_end]`` in
    ``jax.profiler`` trace capture during real training.  The loop calls
    ``maybe_start(upcoming_iteration)`` before dispatch and
    ``maybe_stop(completed_iteration, sync=...)`` after; ``sync`` blocks
    on the step's outputs so the traced window contains the device work,
    not just its dispatch.  One-shot: the window fires once per run."""

    def __init__(self, profile_dir: str, step_start: int, step_end: int,
                 port: Optional[int] = None):
        if step_end < step_start:
            raise ValueError(
                f"profile_step_end ({step_end}) < profile_step_start "
                f"({step_start})")
        self.profile_dir = profile_dir
        self.step_start = int(step_start)
        self.step_end = int(step_end)
        self.active = False
        self.done = False
        self._server = None
        if port:
            # live-capture endpoint (TensorBoard "capture profile")
            self._server = jax.profiler.start_server(int(port))

    def maybe_start(self, upcoming_iteration: int) -> bool:
        if self.done or self.active \
                or upcoming_iteration != self.step_start:
            return False
        os.makedirs(self.profile_dir, exist_ok=True)
        jax.profiler.start_trace(self.profile_dir)
        self.active = True
        print(f" [profiler] trace started at iteration "
              f"{upcoming_iteration} -> {self.profile_dir}", flush=True)
        return True

    def maybe_stop(self, completed_iteration: int,
                   sync: Optional[Callable[[], Any]] = None) -> bool:
        if not self.active or completed_iteration < self.step_end:
            return False
        if sync is not None:
            sync()      # device work of the window lands inside the trace
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        print(f" [profiler] trace stopped after iteration "
              f"{completed_iteration} (view: tensorboard --logdir "
              f"{self.profile_dir}, profile plugin / Perfetto)", flush=True)
        return True

    def close(self) -> None:
        """Stop an in-flight trace on any exit path (a truncated window
        still yields a usable xplane)."""
        if self.active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self.active = False
            self.done = True


# ---------------------------------------------------------------------------
# Trainer live-status endpoint (--status_port)
# ---------------------------------------------------------------------------

class StatusServer:
    """Stdlib HTTP ``/health`` + ``/metrics`` over the latest telemetry
    record — the trainer-side twin of the serving server's endpoints, so
    the same scraper config covers both halves of the system.  Runs as a
    daemon thread on process 0 only; ``update()`` is called from the
    stream's ``emit()`` so it costs one dict assignment per log boundary.

    ``/health``  -> {"status": "ok", "iteration", "secs_since_last_record",
                     "uptime_secs"}
    ``/metrics`` -> the latest record as JSON, or Prometheus text
                    exposition under the usual negotiation
                    (?format=prometheus or an Accept preferring
                    text/plain), prefix ``megatron_train_``.
    """

    def __init__(self, port: int, host: str = "0.0.0.0"):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self._latest: Optional[Dict[str, Any]] = None
        self._latest_at: Optional[float] = None
        self._t_start = time.time()
        self._lock = threading.Lock()
        status = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *a):     # silence per-request noise
                pass

            def _send(self, code, payload, content_type="application/json"):
                body = payload if isinstance(payload, bytes) \
                    else json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path
                if path.partition("?")[0] == "/health":
                    self._send(200, status.health())
                elif path.partition("?")[0] == "/metrics":
                    latest = status.latest() or {}
                    if _wants_prometheus(path,
                                         self.headers.get("Accept", "")):
                        text = prometheus_exposition(
                            latest, prefix="megatron_train_")
                        self._send(200, text.encode(),
                                   content_type="text/plain; version=0.0.4")
                    else:
                        self._send(200, latest)
                else:
                    self._send(404, {"message": "not found"})

        self.httpd = ThreadingHTTPServer((host, int(port)), Handler)
        self.httpd.daemon_threads = True
        self.port = self.httpd.server_address[1]    # resolved when port=0
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, name="status-server",
            daemon=True)
        self._thread.start()

    def update(self, rec: Dict[str, Any]) -> None:
        # keep only JSON-serializable leaves; the record already is
        with self._lock:
            self._latest = rec
            self._latest_at = time.time()

    def latest(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return dict(self._latest) if self._latest else None

    def health(self) -> Dict[str, Any]:
        with self._lock:
            latest, at = self._latest, self._latest_at
        return {
            "status": "ok",
            "iteration": (latest or {}).get("iteration"),
            "secs_since_last_record":
                (round(time.time() - at, 3) if at else None),
            "uptime_secs": round(time.time() - self._t_start, 3),
        }

    def close(self) -> None:
        try:
            self.httpd.shutdown()
            self.httpd.server_close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Bundle + CLI wiring
# ---------------------------------------------------------------------------

@dataclass
class Telemetry:
    """Everything the train loop needs, in one optional argument."""

    throughput: Optional[ThroughputCalculator] = None
    stream: Optional[TelemetryStream] = None
    profiler: Optional[ProfilerSession] = None
    tracing: Optional[Any] = None       # a tracing.Tracing bundle
    status: Optional[StatusServer] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def default(cls, model) -> "Telemetry":
        """Throughput-only telemetry (free): every run reports
        tokens/sec/device + MFU at log boundaries even with no flags."""
        return cls(throughput=ThroughputCalculator.from_model(model))

    def close(self) -> None:
        if self.profiler is not None:
            self.profiler.close()
        if self.tracing is not None:
            # writes the trace file, then uninstalls the module registry
            self.tracing.close()
        if self.status is not None:
            self.status.close()
        if self.stream is not None:
            if get_stream() is self.stream:
                install_stream(None)
            self.stream.close()


def recovery_counters() -> Dict[str, int]:
    from megatron_llm_tpu.resilience import recovery_counters as rc

    return rc()


def build_telemetry(args, model) -> Telemetry:
    """CLI wiring: a Telemetry bundle from parsed args.  Always returns a
    bundle (throughput accounting is free); the stream/profiler members
    exist only when their flags ask for them."""
    t = Telemetry.default(model)
    log_dir = getattr(args, "structured_log_dir", None)
    if log_dir:
        t.stream = TelemetryStream(
            log_dir,
            flight_recorder_size=getattr(args, "flight_recorder_size", 64))
        install_stream(t.stream)
    if getattr(args, "profile", False):
        profile_dir = getattr(args, "profile_dir", None) \
            or (os.path.join(log_dir, "profile") if log_dir
                else "profile_trace")
        t.profiler = ProfilerSession(
            profile_dir,
            step_start=getattr(args, "profile_step_start", 10),
            step_end=getattr(args, "profile_step_end", 12),
            port=getattr(args, "profiler_port", None),
        )
    elif getattr(args, "profiler_port", None):
        # a live-capture server without a pre-chosen window
        jax.profiler.start_server(int(args.profiler_port))
    status_port = getattr(args, "status_port", None)
    if status_port is not None and jax.process_index() == 0:
        if t.stream is None:
            # in-memory stream: the endpoint needs emit() records even
            # when nothing asked for the JSONL file
            t.stream = TelemetryStream(
                None,
                flight_recorder_size=getattr(
                    args, "flight_recorder_size", 64))
            install_stream(t.stream)
        t.status = StatusServer(int(status_port))
        t.stream.status_server = t.status
        print(f" [telemetry] status endpoint on port {t.status.port} "
              f"(/health, /metrics)", flush=True)
    from megatron_llm_tpu import tracing as _tracing

    t.tracing = _tracing.build_tracing(args)    # None without --trace_dir
    return t
