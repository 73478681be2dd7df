"""The mean of one field of the serve loop's launch records
(``megatron_llm_tpu/serving/loop_profiler.py``: the ring that
``loop_phase.py`` reads), cut to the counted (untraced) window: over the
launches whose ``begin`` lies inside the window (and whose kind is one of
``kinds``), ``scale`` x mean(``field``).

It reads as nothing, and the metric is left out, on a program that keeps
no ring, on one whose records lack the field (the parent of the PR that
brought it), and where the field is 0 throughout (a model that has none
of what it counts)."""
import importlib

_loop = importlib.import_module("harness.spec").load_module(
    "sources", "loop_phase")


def mean(records, field):
    """The field's mean over ``records``; None where there is none, a
    record lacks it, or it is 0 throughout."""
    values = [getattr(r, field, None) for r in records]
    if not values or any(v is None for v in values) or not any(values):
        return None
    return sum(values) / len(values)


def read(run, field, kinds=None, scale=1.0):
    if run.window is None:
        return None
    found = mean(_loop.launches(run.window.opened.at, run.window.closed.at,
                                kinds), field)
    return None if found is None else scale * found
