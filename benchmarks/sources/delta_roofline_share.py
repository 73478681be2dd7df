"""The decode step's gated delta-rule recurrences' share of their
roofline: the least time the chip could take for what the decode
launches of the traced stretch had to read and write
(``harness/delta_roofline.py``, from each launch record's own
``delta_rows_live``) over the device seconds of the operations under
``scopes`` (``delta_step``) that the instruction tables give to those
same launches (``sources/op_role_time.py::attribute``).  Live rows are
counted, so it reads the same work whatever implements the step (the
kernel ``delta_state_step`` or XLA's), and can only read low.

``retention_roofline_share.py`` is the same walk over another field and
other bytes; it reads ``bytes.phi_rows`` and its field by name, so it
cannot be given these as ``params`` without an edit (PERF.md's open
questions have the merge a ``benchmark`` PR could make).

Reads as nothing where there is no trace, no ring, no table, records
without the field (the parent of the PR that brought it), a model without
delta layers (the field 0 throughout, or a configuration without
``linear_num_value_heads``), or no operation under the scopes."""
import importlib

_delta = importlib.import_module("harness.delta_roofline")
_probe = importlib.import_module("harness.probe")
_roles = importlib.import_module("harness.spec").load_module(
    "sources", "op_role_time")


def least_and_measured(cfg, rows, ops, window, scopes, peaks):
    """(summed least seconds, summed device seconds) over the decode
    launches of ``rows`` (``(record, dispatch start, fetch end)`` on the
    trace's clock) that lie wholly inside ``window``; None where a record
    lacks the field or no such launch advanced a state."""
    lo, hi = window
    whole = {i for i, (r, ds, fe) in enumerate(rows)
             if r.kind == "decode" and ds >= lo and fe <= hi}
    least = 0.0
    for i in whole:
        live = getattr(rows[i][0], "delta_rows_live", None)
        if live is None:
            return None
        least += _delta.decode_least_seconds(cfg, live, peaks)
    measured = sum(e - s for _, s, e, row, i, _ in ops
                   if i in whole and row is not None
                   and row["scope"] in scopes)
    return (least, measured) if least and measured else None


def read(run, scopes):
    if run.trace is None or run.peaks is None:
        return None
    cfg = _probe.reference_cfg(run)
    if not cfg.get("linear_num_value_heads"):
        return None
    found = _roles.attribute(run)
    if found is None or not found.get("rows"):
        return None
    both = least_and_measured(cfg, found["rows"], found["devices"][0],
                              run.trace.window, tuple(scopes), run.peaks)
    return None if both is None else 100.0 * both[0] / both[1]
