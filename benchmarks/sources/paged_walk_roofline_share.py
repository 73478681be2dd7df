"""The paged walks' share of their roofline over a pool of 64-wide heads:
the least time the chip could take for the keys and values the launches
that began in the traced stretch had to read
(``harness/paged_walk_roofline.py``, from each launch record's own
context lengths) over the device seconds of the operations matching
``pattern`` in the trace.  Launches of every kind: a decode step's walk
and a chunk's are one kernel body.

Reads as nothing where there is no trace, no ring, a configuration
without ``layer_types`` or with no attention layer, or no operation of
that name."""
import importlib

_walk = importlib.import_module("harness.paged_walk_roofline")
_probe = importlib.import_module("harness.probe")
_loop = importlib.import_module("harness.spec").load_module(
    "sources", "loop_phase")


def least_total(cfg, records, peaks):
    """Summed least seconds of the records' walks, or None where there
    is nothing to walk."""
    tokens = sum(_walk.launch_tokens(r) for r in records)
    return _walk.least_seconds(cfg, tokens, peaks) or None


def read(run, pattern):
    t0, t1 = run.setup_parts.get("traced", (None, None))
    if run.trace is None or t0 is None or t1 is None or run.peaks is None:
        return None
    cfg = _probe.reference_cfg(run)
    if not isinstance(cfg.get("layer_types"), list) or not (
            _walk.attention_layers(cfg)):
        return None
    measured = run.trace.op_seconds(pattern)
    least = least_total(cfg, _loop.launches(t0, t1), run.peaks)
    if not measured or least is None:
        return None
    return 100.0 * least / measured
