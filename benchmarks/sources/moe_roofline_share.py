"""The experts' grouped matmul kernel's share of its roofline: the least
time the chip could take for the expert matrices of the launches that
began in the traced stretch (``harness/moe_roofline.py``, from each
launch record's own ``moe_assignments`` and ``moe_experts_touched``)
over the device seconds of the operations matching ``pattern`` in the
trace.  Launches of every kind: the trace's operations carry no program,
so the kernel's seconds are those of decode steps and chunks together,
and so is the least time.

Reads as nothing where there is no trace, no ring, records without the
two fields (the parent of the PR that brought them), nothing routed, or
no operation of that name."""
import importlib

_moe = importlib.import_module("harness.moe_roofline")
_probe = importlib.import_module("harness.probe")
_loop = importlib.import_module("harness.spec").load_module(
    "sources", "loop_phase")


def least_total(run, records):
    """Summed least seconds of the records' expert matrices, or None
    where a record lacks the fields or nothing was routed."""
    cfg = _probe.reference_cfg(run)
    total = 0.0
    for r in records:
        a = getattr(r, "moe_assignments", None)
        e = getattr(r, "moe_experts_touched", None)
        if a is None or e is None:
            return None
        if a:
            total += _moe.least_seconds(cfg, a, e, run.peaks)[0]
    return total or None


def read(run, pattern):
    t0, t1 = run.setup_parts.get("traced", (None, None))
    if run.trace is None or t0 is None or t1 is None or run.peaks is None:
        return None
    measured = run.trace.op_seconds(pattern)
    least = least_total(run, _loop.launches(t0, t1))
    if not measured or least is None:
        return None
    return 100.0 * least / measured
