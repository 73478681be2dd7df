"""Percentile of the gaps between consecutive output tokens of one
request, pooled over all requests, a gap counting if its later token fell
inside the window."""
import importlib

_w = importlib.import_module("harness.window")


def read(run, q, scale=1000.0):
    if run.window is None:
        return None
    p = _w.percentile(_w.token_gaps(run.records, run.window), q)
    return None if p is None else p * scale
