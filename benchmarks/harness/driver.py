"""What every serving loop shares: the counters' snapshot, the stamped
event queue, the driver that submits a planned request and keeps its
record, and the profiler's stretch AFTER the window.

A traced run counts the same window as an untraced one and then goes on
offering the same traffic while the profiler records ``trace_seconds``:
starting and stopping the profiler stalls the host for seconds, and
inside the window that would be read as the program's (PR 23's first
traced training run lost 3 s of a 20 s window to it)."""

from __future__ import annotations

import queue
import threading
import time
from typing import List

from .context import Run, sleep_until, start_trace
from .window import CounterSnapshot, RequestRecord

COUNTERS = ("decode_steps", "prefill_chunks", "tokens_generated",
            "prefill_tokens_submitted", "prefill_tokens_computed",
            "prefill_tokens_cached", "occupancy_sum", "decode_secs",
            "prefill_secs")


class StampedEvents(queue.Queue):
    """A request's event queue that notes the host clock of every event
    as the engine puts it: what a streaming client would see at the
    earliest, with no reader thread per request on the host."""

    def __init__(self, record: RequestRecord, done: threading.Event):
        super().__init__()
        self.record, self.done = record, done

    def put(self, item, block=True, timeout=None):
        now = time.perf_counter()
        kind, payload = item
        if kind == "token":
            self.record.token_times.append(now)
            self.record.out_tokens.append(int(payload))
        elif kind == "done":
            self.record.finish_reason = payload
        super().put(item, block, timeout)
        if kind == "done":
            self.done.set()


def snapshot(engine) -> CounterSnapshot:
    at = time.perf_counter()
    return CounterSnapshot(at, {k: float(getattr(engine, k))
                                for k in COUNTERS})


class Driver:
    def __init__(self, run: Run, engine, sampling_cls):
        self.run, self.engine, self.sampling_cls = run, engine, sampling_cls
        self.lock = threading.Lock()
        self.live: List[tuple] = []     # (record, request, done event)

    def submit(self, planned, due: float) -> tuple:
        rec = RequestRecord(planned.index, len(planned.prompt),
                            planned.answer_tokens, due)
        done = threading.Event()
        rec.submitted = time.perf_counter()
        try:
            req = self.engine.submit(
                planned.prompt,
                self.sampling_cls(max_new_tokens=planned.answer_tokens,
                                  temperature=0.0),
                stream=True)
        except Exception as e:  # noqa: BLE001 - a refusal is a failed request
            rec.refused = f"{type(e).__name__}: {e}"
            done.set()
            req = None
        else:
            stamped = StampedEvents(rec, done)
            old, req._events = req._events, stamped
            while True:                 # events put before the swap
                try:
                    stamped.put(old.get_nowait())
                except queue.Empty:
                    break
        with self.lock:
            self.run.records.append(rec)
            self.live.append((rec, req, done))
        return rec, req, done

    def drain(self, seconds: float) -> None:
        deadline = time.perf_counter() + seconds
        for rec, req, done in list(self.live):
            done.wait(max(deadline - time.perf_counter(), 0.0))
            if req is not None:
                rec.queue_wait_secs = req.queue_wait_secs


def trace_after(run: Run, trace_dir: str, seconds: float,
                on_start=None) -> None:
    """Profile ``seconds`` of the traffic that goes on after the window
    has closed.  ``on_start`` is called with the host clock at which the
    profiler began to record: starting it takes a time that is not the
    benchmark's to fix, so a loop whose arrivals are on a schedule lays
    the stretch's arrivals from that instant."""
    import jax

    asked = time.perf_counter()
    start_trace(trace_dir)
    run.tracing_now = True
    t0 = time.perf_counter()
    run.setup_parts["profiler_start_s"] = t0 - asked
    if on_start is not None:
        on_start(t0)
    sleep_until(t0 + seconds)
    t1 = time.perf_counter()
    run.tracing_now = False
    jax.profiler.stop_trace()
    run.setup_parts["traced"] = (t0, t1)
