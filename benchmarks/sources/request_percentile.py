"""Percentile over the requests due in the window of a request clock:
'ttft_from_due' (first token minus due time) or 'queue_wait' (the
engine's own queue_wait_secs), in ``scale`` units (1000 = ms)."""
import importlib

_w = importlib.import_module("harness.window")


def read(run, clock, q, scale=1000.0):
    if run.window is None:
        return None
    if clock == "ttft_from_due":
        values = _w.ttft_from_due(run.records, run.window)
    elif clock == "queue_wait":
        values = [r.queue_wait_secs
                  for r in _w.due_in_window(run.records, run.window)
                  if r.queue_wait_secs is not None]
    else:
        raise ValueError(clock)
    p = _w.percentile(values, q)
    return None if p is None else p * scale
