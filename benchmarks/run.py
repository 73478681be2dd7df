#!/usr/bin/env python3
"""One cell of the benchmark, once, in this process.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Prints notes (one JSON object a line) and, as the LAST line of standard
output, the result: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.  With no TPU,
or fewer chips than the cell asks for, it exits non-zero and prints no
result.

    python benchmarks/run.py --workload <name> --rehearse

walks the same code on the CPU at the configuration's tiny rehearsal
sizes (virtual devices for a four-chip cell, Pallas in interpret mode).
A rehearsal prints a line marked ``"rehearsal": true`` whose ``correct``
is always false, and never exits 0.

Which cell, configuration, traffic and metrics there are is read from
``BENCHMARK.json`` and the files under ``benchmarks/`` (see README.md);
nothing in this file names one.
"""

import time

PROCESS_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
import tempfile     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

NO_CHIP = 4
REHEARSAL = 5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from harness import spec
    from harness.context import CompileMeter, Run, note

    cell = spec.load_cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell.chips}")
    sys.path.insert(0, spec.ROOT)
    import jax

    if args.rehearse:
        from megatron_llm_tpu.ops.pallas import (
            flash_attention, paged_attention, rmsnorm)
        for mod in (flash_attention, paged_attention, rmsnorm):
            mod._INTERPRET = True
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] < cell.chips):
        print(f"benchmarks/run.py: {args.workload} needs {cell.chips} TPU "
              f"chip(s); JAX reports {device}", file=sys.stderr)
        return NO_CHIP

    seconds = args.seconds if args.seconds is not None else cell.run_seconds
    if args.rehearse and args.seconds is None:
        seconds = float(cell.traffic.get("rehearsal", {}).get("seconds", 3))
    run = Run(cell=cell, seed=args.seed, seconds=float(seconds),
              traced=bool(args.trace), rehearsal=args.rehearse,
              process_start=PROCESS_START, device=device,
              meter=CompileMeter())
    if not args.rehearse:
        run.peaks = spec.peaks_for(device["kind"])
    program = cell.config["program"]
    flags = list(program["rehearsal_flags"] if args.rehearse
                 else program["flags"])
    note("start", workload=cell.name, seed=args.seed, seconds=seconds,
         trace=args.trace, rehearsal=args.rehearse, device=device)

    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        trace_dir = os.path.join(tmp, "trace") if args.trace else None
        # the configuration's entry names how its program is built and
        # driven: entries/<entry>.py
        spec.load_module("entries", program["entry"]).run_entry(
            run, flags, tmp, trace_dir)
        if trace_dir:
            from harness import trace
            run.trace = trace.reduce_dir(trace_dir)

    from harness import shape
    if not args.rehearse:
        run.checks["program_ran_the_file_sizes"] = not \
            shape.differs_from_published(run.model_shape, cell.config)
    opened = run.setup_parts["window_opened_at"]
    note("setup", total_s=run.setup_s,
         compile_s=run.meter.seconds_before(opened),
         cache_hits=run.meter.cache_hits, cache_misses=run.meter.cache_misses,
         **{k: v for k, v in run.setup_parts.items()
            if isinstance(v, float) and k != "window_opened_at"})
    note("checks", **run.checks)

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = m.read(run)
        if value is not None:
            # a number from a CPU run never goes under a metric's name
            metrics[m.name] = {"value": None if args.rehearse else value,
                               "unit": m.unit}
    device_out = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    result = {"correct": run.correct(), "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device_out}
    if args.trace and run.trace is not None:
        device_out["busy_s"] = run.trace.busy_s
        device_out["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_gaps(10)}
    if args.rehearse:
        result["rehearsal"] = True
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    if args.rehearse:
        return REHEARSAL
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (engine, telemetry) must not hold the
    # exit; everything this file started has been joined
    os._exit(code)
