"""A ratio of two fields of the serve loop's launch records
(``megatron_llm_tpu/serving/loop_profiler.py``: the ring that
``loop_phase.py`` reads), cut to the counted (untraced) window: over the
launches whose ``begin`` lies inside the window (and whose kind is one of
``kinds``), ``scale`` x sum(``numerator``) / sum(``denominator``).

It reads as nothing, and the metric is left out, on a program that keeps
no ring, on one whose records lack either field (the parent of the PR
that brought them), and where the denominator is 0 (a dense model routes
nothing)."""
import importlib

_loop = importlib.import_module("harness.spec").load_module(
    "sources", "loop_phase")


def sums(records, numerator, denominator):
    """(sum of numerator, sum of denominator) over ``records``, or None
    where a record lacks a field."""
    num = den = 0
    for r in records:
        n, d = getattr(r, numerator, None), getattr(r, denominator, None)
        if n is None or d is None:
            return None
        num, den = num + n, den + d
    return num, den


def read(run, numerator, denominator, kinds=None, scale=1.0):
    if run.window is None:
        return None
    found = sums(_loop.launches(run.window.opened.at, run.window.closed.at,
                                kinds), numerator, denominator)
    if found is None or not found[1]:
        return None
    return scale * found[0] / found[1]
