"""Closed loop: a fixed number of callers, each sending its next
document when the last is answered.  The window opens once
``open_after_answers`` answers have come back, on a full engine; a
traced run keeps the callers going for the profiler's stretch after the
window has closed."""

from __future__ import annotations

import threading
import time
from typing import Optional

from harness import traffic
from harness.context import Run, note, sleep_until
from harness.driver import Driver, snapshot, trace_after
from harness.window import Window


def drive(run: Run, driver: Driver, spec_t: dict, vocab: int,
          trace_dir: Optional[str]) -> None:
    source = traffic.ClosedLoopSource(spec_t, run.seed, vocab)
    callers = int(spec_t["callers"])
    open_after = int(spec_t.get("open_after_answers", callers))
    timeout = float(spec_t.get("answer_timeout_seconds", 300))
    stop = threading.Event()
    answers = threading.Semaphore(0)
    in_flight = [0, 0]                  # now, most ever
    t_lead = time.perf_counter()

    def call():
        while not stop.is_set():
            with driver.lock:
                planned = source.next()
                in_flight[0] += 1
                in_flight[1] = max(in_flight)
            _, _, done = driver.submit(planned, time.perf_counter())
            done.wait(timeout)
            with driver.lock:
                in_flight[0] -= 1
            answers.release()

    threads = [threading.Thread(target=call, name=f"bench-caller-{i}",
                                daemon=True) for i in range(callers)]
    for t in threads:
        t.start()
    for _ in range(open_after):         # the window opens on a full engine
        if not answers.acquire(timeout=600):
            raise RuntimeError("the closed loop got no answers in 600 s")
    opened = snapshot(driver.engine)
    run.setup_parts["lead_in_s"] = opened.at - t_lead
    run.setup_parts["window_opened_at"] = opened.at
    sleep_until(opened.at + run.seconds)
    run.window = Window(opened, snapshot(driver.engine))
    if trace_dir:
        trace_after(run, trace_dir, float(spec_t.get("trace_seconds", 3.0)))
    stop.set()
    for t in threads:
        t.join(timeout)
    run.checks["closed_loop_within_callers"] = in_flight[1] <= callers
    note("traffic", kind="closed_loop", seed=run.seed, callers=callers,
         most_in_flight=in_flight[1], documents=source.handed_out)
