"""Drive the trainer as a user does: ``finetune.main()`` with the
configuration's flags on synthetic data, its structured log for step
times and loss.  The train step is wrapped (as ``chip_smoke._Watched``
wraps it) to open and close the window on step boundaries.  A traced
run counts the same window and then runs ``trace_steps`` more steps
under the profiler as ``bench.train_step``: starting and stopping the
profiler costs seconds, which inside the window would be read as the
program's.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time
from typing import List, Optional

import numpy as np

from harness import probe, shape, spec
from harness.context import Run, note, read_peak_memory, start_trace


class WindowClosed(Exception):
    """Raised out of the wrapped train step to end ``finetune.main()``."""


class _Step:
    """The jitted train step.  With ``--log_interval=1`` the loop blocks
    on every step's loss, so on entry to call k every earlier step has
    finished: entries are step boundaries on the host clock."""

    def __init__(self, fn, run: Run, warm_steps: int, trace_dir,
                 trace_steps: int):
        self.fn, self.run = fn, run
        self.warm_steps, self.trace_dir = warm_steps, trace_dir
        self.trace_steps = trace_steps
        self.calls = 0
        self.first_batch = None
        self.opened: Optional[float] = None
        self.closed: Optional[float] = None
        self.boundaries: List[float] = []
        self.traced_steps = 0

    def __call__(self, params, opt_state, batch, *rest):
        import jax

        now = time.perf_counter()
        if self.first_batch is None:
            self.first_batch = {k: np.asarray(v) for k, v in batch.items()}
            self.tokens_per_step = int(batch["tokens"].size)
            self.seq_length = int(batch["tokens"].shape[-1])
        if self.opened is None and self.calls == self.warm_steps:
            self.opened = now
            self.run.setup_parts["window_opened_at"] = now
        elif self.opened is not None and self.closed is None:
            self.boundaries.append(now)
            if now - self.opened >= self.run.seconds:
                self.closed = now
                if not self.trace_dir:
                    raise WindowClosed()
                start_trace(self.trace_dir)
                self.run.setup_parts["traced"] = (time.perf_counter(), None)
        self.calls += 1
        if self.closed is None:
            return self.fn(params, opt_state, batch, *rest)
        # the window is counted; the profiler's steps follow it
        if self.traced_steps >= self.trace_steps:
            jax.profiler.stop_trace()
            self.run.setup_parts["traced"] = (
                self.run.setup_parts["traced"][0], time.perf_counter())
            raise WindowClosed()
        self.traced_steps += 1
        with jax.profiler.TraceAnnotation("bench.train_step"):
            out = self.fn(params, opt_state, batch, *rest)
            jax.block_until_ready(out[2])
        return out


def run_entry(run: Run, flags: List[str], tmp: str,
              trace_dir: Optional[str]) -> None:
    import jax

    log_dir = os.path.join(tmp, "log")
    spec_t = run.cell.traffic_for_config()
    if run.rehearsal:
        spec_t.update(spec_t.get("rehearsal", {}))
    sys.path.insert(0, spec.ROOT)
    import finetune
    from megatron_llm_tpu import global_vars, training
    from megatron_llm_tpu.parallel import sharding as sh

    run.setup_parts["import_s"] = time.perf_counter() - run.process_start
    steps: List[_Step] = []
    build = training.build_train_step

    def watched_build(*a, **kw):
        steps.append(_Step(build(*a, **kw), run,
                           int(spec_t.get("warm_steps", 3)), trace_dir,
                           int(spec_t.get("trace_steps", 3))))
        return steps[-1]

    training.build_train_step = watched_build
    seed = run.seed % (2 ** 31 - 1)
    sys.argv = (["finetune.py"] + list(flags)
                + [f"--{k}={v}" for k, v in spec_t.get("flags", {}).items()]
                + [f"--seed={seed}", f"--structured_log_dir={log_dir}",
                   "--log_interval=1", "--train_iters=1000000"])
    try:
        finetune.main()
        ended_by_window = False
    except WindowClosed as e:
        ended_by_window = True
        e.__traceback__ = None          # let go of the frames' train state
    finally:
        training.build_train_step = build
    gc.collect()
    step = steps[0]
    with open(os.path.join(log_dir, "telemetry.jsonl")) as f:
        run.train_log = [r for r in map(json.loads, f)
                         if r.get("kind") == "log"]

    args = global_vars.get_args()
    model = finetune.model_provider(args)
    run.model_shape = shape.model_shape(model.cfg)
    done = len(step.boundaries)
    closed = step.closed if step.closed is not None else step.opened
    run.train_window = {
        "steps": done, "tokens_per_step": step.tokens_per_step,
        "seconds": closed - step.opened, "opened": step.opened,
        "closed": closed, "chips": jax.device_count(),
        "warm_steps": step.warm_steps, "seq_length": step.seq_length,
    }
    read_peak_memory(run)
    in_window = run.train_log[step.warm_steps:step.warm_steps + done]
    losses = [r["lm_loss"] for r in run.train_log]
    note("window", seconds=run.train_window["seconds"], steps=done,
         tokens_per_step=step.tokens_per_step, traced_steps=step.traced_steps,
         tokens_per_s=(done * step.tokens_per_step
                       / run.train_window["seconds"]) if done else None,
         step_time_p50_s=float(np.median(
             [r["step_time_secs"] for r in in_window])) if in_window else None,
         first_loss=losses[0] if losses else None,
         last_loss=losses[-1] if losses else None)
    run.attempted = step.warm_steps + done + step.traced_steps
    run.failed = sum(1 for r in run.train_log
                     if not np.isfinite(r["lm_loss"])
                     or int(r.get("skipped_iter", 0)))
    run.checks.update({
        "window_closed_the_run": ended_by_window,
        "every_step_logged": len(run.train_log) >= run.attempted,
        "no_step_failed": run.failed == 0 and done > 0,
        "no_compile_in_window": run.meter.count_between(
            step.opened, closed) == 0,
    })
    note("memory", live_bytes_before_probe=sum(
        a.nbytes for a in jax.live_arrays()))
    # the first step's weights again, from the seed (the step donated
    # them), as finetune.main() makes them
    params = sh.init_params(model, jax.random.PRNGKey(args.seed))
    if args.bf16:
        import jax.numpy as jnp
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16), params)
    try:
        probe.training_probe(run, model, params, step.first_batch, losses[0],
                             bool(args.sequence_parallel))
    except Exception as e:  # noqa: BLE001 - the window's numbers still print
        run.checks["probe_ran"] = False
        note("probe_failed", error=f"{type(e).__name__}: {e}"[:2000])
