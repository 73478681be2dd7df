"""A step program's share of its roofline: the least time the chip could
take for the step (operations over peak FLOP/s or bytes over peak
bytes/s, whichever is larger) over the device time measured under the
step's annotation.  ``program`` is 'decode' or 'prefill'; the step's
shapes are those the benchmark noted for the steps inside the traced
stretch.  Medians of both."""
import importlib
import statistics

_roof = importlib.import_module("harness.roofline")
_probe = importlib.import_module("harness.probe")
_adt = importlib.import_module("harness.spec").load_module(
    "sources", "annotation_device_time")


def least_times(run, program, annotation):
    t0, t1 = run.setup_parts.get("traced", (None, None))
    if t0 is None or t1 is None or run.peaks is None:
        return []
    cfg = _probe.reference_cfg(run)
    out = []
    for s in run.step_samples.get(annotation, []):
        if not t0 <= s["t"] <= t1:
            continue
        if program == "decode":
            if not s["rows"]:
                continue
            flops, nbytes = _roof.decode_step_cost(cfg, s["context_tokens"])
        else:
            flops, nbytes = _roof.prefill_chunk_cost(cfg, s["start"],
                                                     s["valid"])
        out.append(_roof.least_seconds(flops, nbytes, run.peaks))
    return out


def read(run, program, annotation):
    measured = [s for s in _adt.spans(run, annotation) if s > 0]
    least = least_times(run, program, annotation)
    if not measured or not least:
        return None
    return (100.0 * statistics.median(t for t, _ in least)
            / statistics.median(measured))
