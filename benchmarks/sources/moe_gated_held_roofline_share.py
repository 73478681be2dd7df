"""The experts' grouped matmul kernel's share of the roofline of GATED
experts of which this chip holds a share: the least time the chip could
take for the expert matrices of the launches that began in the traced
stretch (``harness/moe_gated_held_roofline.py``, from each launch
record's own ``moe_assignments_held`` and ``moe_experts_touched_held``)
over the device seconds of the operations matching ``pattern`` in the
trace.  Launches of every kind, as ``moe_roofline_share.py`` says why.

Reads as nothing where there is no trace, no ring, records without
``moe_experts_touched_held``, a configuration without
``moe_intermediate_size``, nothing routed to a held expert, or no
operation of that name."""
import importlib

_moe = importlib.import_module("harness.moe_gated_held_roofline")
_probe = importlib.import_module("harness.probe")
_loop = importlib.import_module("harness.spec").load_module(
    "sources", "loop_phase")


def least_total(cfg, records, peaks):
    """Summed least seconds of the records' held expert matrices, or None
    where a record lacks the fields or nothing fell on a held expert."""
    total = 0.0
    for r in records:
        a = getattr(r, "moe_assignments_held", None)
        e = getattr(r, "moe_experts_touched_held", None)
        if a is None or e is None:
            return None
        if a:
            total += _moe.least_seconds(cfg, a, e, peaks)[0]
    return total or None


def read(run, pattern):
    t0, t1 = run.setup_parts.get("traced", (None, None))
    if run.trace is None or t0 is None or t1 is None or run.peaks is None:
        return None
    cfg = _probe.reference_cfg(run)
    if "moe_intermediate_size" not in cfg:
        return None
    measured = run.trace.op_seconds(pattern)
    least = least_total(cfg, _loop.launches(t0, t1), run.peaks)
    if not measured or least is None:
        return None
    return 100.0 * least / measured
