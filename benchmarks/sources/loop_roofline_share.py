"""A looped stack's share of its roofline, by the launches that began in
the traced stretch (``harness/loop_roofline.py``, from each launch
record's own ``rows`` / ``context_tokens`` or ``start`` / ``valid``).

``what``:

* ``decode`` / ``prefill``: the MEAN least seconds of the launches of
  that kind over the MEAN device seconds under the benchmark's
  annotation of that program's step (``bench.decode_step`` /
  ``bench.prefill_step``: the device's busy time from the launch to the
  next one, so a chunk's copy of the pool it is lent is in the
  denominator and in no numerator).  Means, and not sums: the trace's
  first and last spans may be cut by its edges and are left out;
* ``walk``: the SUMMED least seconds of every launch's walks (the bytes
  of the tokens it reads in every plane) over the device seconds of the
  operations matching ``pattern`` in the trace.

Reads as nothing where there is no trace, no ring, a record without the
field ``loop_layer_runs`` (the parent of the PR that brought the looped
stack), a model that loops nothing (the field 0 throughout), a
configuration without ``total_ut_steps``, or nothing measured."""
import importlib
import statistics

_cost = importlib.import_module("harness.loop_roofline")
_probe = importlib.import_module("harness.probe")
_spec = importlib.import_module("harness.spec")
_loop = _spec.load_module("sources", "loop_phase")
_adt = _spec.load_module("sources", "annotation_device_time")

KINDS = {"decode": ("decode",), "prefill": ("prefill",), "walk": None}


def looped(records) -> bool:
    """Whether the records are a looped model's: every one carries the
    field and some launch ran a layer more than once a row."""
    runs = [getattr(r, "loop_layer_runs", None) for r in records]
    return bool(runs) and None not in runs and any(runs)


def read(run, what, annotation=None, pattern=None):
    t0, t1 = run.setup_parts.get("traced", (None, None))
    if run.trace is None or t0 is None or t1 is None or run.peaks is None:
        return None
    cfg = _probe.reference_cfg(run)
    records = _loop.launches(t0, t1, KINDS[what])
    if not cfg.get("total_ut_steps") or not looped(records):
        return None
    if what == "walk":
        measured = run.trace.op_seconds(pattern)
        least = sum(_cost.walk_least_seconds(cfg, r, run.peaks)
                    for r in records)
        return 100.0 * least / measured if measured else None
    spans = [s for s in _adt.spans(run, annotation) if s > 0]
    if not spans:
        return None
    least = statistics.fmean(
        _cost.launch_least_seconds(cfg, r, run.peaks) for r in records)
    return 100.0 * least / statistics.fmean(spans)
