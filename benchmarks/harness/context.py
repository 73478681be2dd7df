"""The record of one run, which the metric sources read."""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from .window import RequestRecord, Window


def note(note_kind: str, **fields) -> None:
    """An earlier line of standard output: one JSON object, never the
    last line."""
    print(json.dumps({"note": note_kind, **fields}), flush=True)


def sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


class CompileMeter:
    """Backend compile seconds, with the time of each, heard from
    ``jax.monitoring`` (a hit in the persistent cache is a 'compile' of a
    few milliseconds and is heard too)."""

    def __init__(self):
        import jax

        self.events: List[tuple] = []       # (host clock at end, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), float(secs)))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def seconds_before(self, t: float) -> float:
        return sum(s for at, s in self.events if at <= t)

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for at, _ in self.events if t0 < at <= t1)


@dataclass
class Run:
    cell: Any
    seed: int
    seconds: float
    traced: bool
    rehearsal: bool
    process_start: float                # host clock at the top of run.py
    device: Dict[str, Any] = field(default_factory=dict)
    peaks: Optional[Dict[str, Any]] = None
    meter: Optional[CompileMeter] = None
    setup_parts: Dict[str, float] = field(default_factory=dict)
    window: Optional[Window] = None
    records: List[RequestRecord] = field(default_factory=list)
    engine_settings: Dict[str, Any] = field(default_factory=dict)
    model_shape: Dict[str, Any] = field(default_factory=dict)
    # serving, traced run: what each annotated step worked on
    step_samples: Dict[str, List[dict]] = field(default_factory=dict)
    # training
    train_log: List[dict] = field(default_factory=list)
    train_window: Dict[str, float] = field(default_factory=dict)
    trace: Any = None                   # harness.trace.Reduced, traced runs
    tracing_now: bool = False           # the profiler is recording
    memory_peak_bytes: int = 0
    memory_by_device: Dict[str, int] = field(default_factory=dict)
    checks: Dict[str, Any] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0

    @property
    def setup_s(self) -> Optional[float]:
        if "window_opened_at" not in self.setup_parts:
            return None
        return self.setup_parts["window_opened_at"] - self.process_start

    def correct(self) -> bool:
        return (not self.rehearsal and bool(self.checks)
                and all(v is True for v in self.checks.values()))


def read_peak_memory(run: "Run") -> None:
    """Peak bytes in use on each device, as the backend reports them."""
    import jax

    if jax.default_backend() != "tpu":
        return
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        run.memory_by_device[str(d.id)] = int(
            stats.get("peak_bytes_in_use", 0))
    run.memory_peak_bytes = max(run.memory_by_device.values())


def start_trace(trace_dir: str) -> None:
    """The profiler without its Python tracer: host TraceMe events (the
    annotations) and the device, not every Python call."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
