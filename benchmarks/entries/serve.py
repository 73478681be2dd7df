"""Drive the serving engine as a user's server does, in this process.

The server is built by ``tools/run_text_generation_server.build_server``
with the configuration file's flags; requests go in through
``engine.submit(..., stream=True)`` and every token's time is read off
the request's event queue.  The HTTP hop is left out on purpose (see
PERF.md, layer "front door").
"""

from __future__ import annotations

import os
import sys
import time
from typing import List, Optional

from harness import probe, shape, spec
from harness.context import Run, note, read_peak_memory
from harness.driver import COUNTERS, Driver, snapshot
from harness.window import (counters_account_for, failed_requests,
                            generator_lateness, percentile)


class Annotated:
    """A jitted step which, while the profiler is on, runs under a
    ``jax.profiler.TraceAnnotation`` and notes what it worked on."""

    def __init__(self, run: Run, fn, name: str, sample, sink: list):
        import jax

        self.run, self.fn, self.name = run, fn, name
        self.sample, self.sink = sample, sink
        self._annotation = jax.profiler.TraceAnnotation

    def __call__(self, *args):
        if not self.run.tracing_now:
            return self.fn(*args)
        self.sink.append(dict(self.sample(*args), t=time.perf_counter()))
        with self._annotation(self.name):
            return self.fn(*args)


def _decode_sample(params, pages, last_tokens, context_lens, tables,
                   active, *rest):
    live = active > 0
    return {"rows": int(live.sum()),
            "context_tokens": [int(c) for c in context_lens[live]]}


def _prefill_sample(params, pages, toks, start_pos, valid_len, table):
    return {"start": int(start_pos), "valid": int(valid_len)}


def run_entry(run: Run, flags: List[str], tmp: str,
              trace_dir: Optional[str]) -> None:
    t0 = time.perf_counter()
    sys.path.insert(0, os.path.join(spec.ROOT, "tools"))
    import run_text_generation_server as srv
    from megatron_llm_tpu.initialize import initialize_megatron
    from megatron_llm_tpu.serving.request import SamplingParams

    run.setup_parts["import_s"] = time.perf_counter() - run.process_start
    argv = list(flags) + [f"--seed={run.seed % (2 ** 31 - 1)}"]
    server = srv.build_server(
        initialize_megatron(extra_args_provider=srv.extra_args,
                            args_list=argv), argv)
    engine = server.generator.engine
    run.setup_parts["build_and_warm_s"] = time.perf_counter() - t0
    mcfg = engine.model.cfg
    vocab = int(mcfg.padded_vocab_size)
    run.model_shape = shape.model_shape(mcfg)
    run.engine_settings = {
        "num_slots": engine.config.num_slots,
        "block_size": engine.config.block_size,
        "num_blocks": engine._num_blocks,
        "prefill_chunk": engine.config.prefill_chunk,
        "max_model_len": engine.config.max_model_len,
        "paged_kernel": engine.paged_kernel,
        "prefill_kernel": engine.prefill_kernel,
    }
    note("engine", **run.engine_settings)
    if run.traced:
        for attr, name, sample in (
                ("_decode_step", "bench.decode_step", _decode_sample),
                ("_prefill_step", "bench.prefill_step", _prefill_sample)):
            sink = run.step_samples.setdefault(name, [])
            setattr(engine, attr,
                    Annotated(run, getattr(engine, attr), name, sample,
                              sink))

    spec_t = run.cell.traffic_for_config()
    if run.rehearsal:
        spec_t.update(spec_t.get("rehearsal", {}))
    driver = Driver(run, engine, SamplingParams)
    baseline = snapshot(engine)
    # the traffic's kind names its loop: loops/<kind>.py
    spec.load_module("loops", spec_t["kind"]).drive(
        run, driver, spec_t, vocab, trace_dir)
    for name, sink in run.step_samples.items():
        note("step_samples", annotation=name, count=len(sink))

    driver.drain(float(spec_t.get("drain_seconds", 90)))
    final = snapshot(engine)
    since = {k: final.values[k] - baseline.values[k] for k in COUNTERS}
    accounted, detail = counters_account_for(run.records, since)
    read_peak_memory(run)

    w = run.window
    late = generator_lateness(run.records)

    def in_flight(t):
        return sum(1 for r in run.records
                   if r.submitted is not None and r.submitted <= t
                   and not (r.token_times and r.finish_reason
                            and r.token_times[-1] <= t))
    note("window", seconds=w.seconds,
         requests_seen=len(run.records),
         in_flight_at_open=in_flight(w.opened.at),
         in_flight_at_close=in_flight(w.closed.at),
         generator_lateness_p95_ms=(percentile(late, 95) or 0.0) * 1e3,
         finish_reasons=sorted({str(r.finish_reason) for r in run.records}),
         **detail)
    run.attempted = len(run.records)
    run.failed = failed_requests(run.records)
    run.checks.update({
        "counters_account_for_finished_requests": accounted,
        "no_request_failed": run.failed == 0,
        "no_nonfinite_slot": engine.slots_evicted_nonfinite == 0,
        "no_compile_in_window":
            run.meter.count_between(w.opened.at, w.closed.at) == 0,
    })

    probe.serving_probe(run, engine, SamplingParams, vocab)
    engine.stop()
