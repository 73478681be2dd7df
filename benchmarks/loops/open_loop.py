"""Open loop: requests are due at times fixed by the traffic file's
schedule, whatever the engine does.  Lead-in, then the window; a traced
run goes on for the profiler's stretch after the window has closed.  The
stretch's arrivals are laid from the instant the profiler began to
record, so that every traced run profiles the same arrivals however long
the profiler took to start (at 1.1 requests a second a stretch laid from
the window's end had 4 arrivals, and a profiler that started late saw no
prefill chunk at all: the driver's check of PR 23 refused that run)."""

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
    trace_seconds = float(spec_t.get("trace_seconds", 3.0))
    # the schedule outlasts the trace by a second, so that the profiler
    # sees arrivals to its end
    after = trace_seconds + 1.0 if trace_dir else 0.0
    plan = traffic.open_loop_schedule(spec_t, run.seconds, run.seed, vocab,
                                      after_seconds=after)
    lead = float(spec_t.get("lead_in_seconds", 0.0))
    offered = traffic.multiset(plan, "window")
    note("traffic", kind="open_loop", seed=run.seed,
         lead_in=traffic.multiset(plan, "lead_in")["requests"],
         window=offered["requests"],
         after=traffic.multiset(plan, "after")["requests"],
         window_prompt_tokens=sum(offered["prompt_tokens"]),
         window_answer_tokens=sum(offered["answer_tokens"]))
    t_sched = time.perf_counter() + 0.25
    profiling = threading.Event()
    recording_from = []                 # host clock, once the profiler is on

    def generate():
        base = t_sched
        for p in plan:
            if p.section == "after" and base == t_sched:
                if not profiling.wait(120.0):
                    return
                base = recording_from[0] - (lead + run.seconds)
            sleep_until(base + p.due)
            driver.submit(p, base + p.due)

    def recording(t0: float) -> None:
        recording_from.append(t0)
        profiling.set()

    gen = threading.Thread(target=generate, name="bench-generator",
                           daemon=True)
    gen.start()
    sleep_until(t_sched + lead)
    opened = snapshot(driver.engine)
    run.setup_parts["lead_in_s"] = lead
    run.setup_parts["window_opened_at"] = opened.at
    sleep_until(opened.at + run.seconds)
    run.window = Window(opened, snapshot(driver.engine))
    if trace_dir:
        trace_after(run, trace_dir, trace_seconds, on_start=recording)
    gen.join(30.0)
    if gen.is_alive():
        raise RuntimeError("the generator did not end with its schedule")
