"""A phase of the serve loop from the engine's own span ring
(``megatron_llm_tpu/serving/loop_profiler.py``), cut to the counted
(untraced) window: over the launches whose ``begin`` lies inside the
window (and whose kind is one of ``kinds``), the mean or median seconds
in ``phase``, in ``scale`` units (1000 = ms).  ``phase`` is one of the
loop's six phases, or ``gap``: finish of one launch to begin of the
next, a gap the engine spent waiting for work left out (``idle()``
broke the chain there and the record's gap is exactly 0).

The sources are handed only ``run``, so the ring is reached through the
module's registry; a program that has none (the parent of the PR that
brought the spans) reads as nothing and the metric is left out."""
import statistics

PICK = {"mean": statistics.fmean, "median": statistics.median}


def profiler():
    """The span ring of the engine that served this run, or None where
    the program keeps none."""
    try:
        from megatron_llm_tpu.serving import loop_profiler
    except ImportError:
        return None
    live = getattr(loop_profiler, "live_profilers", None)
    found = live() if live is not None else []
    return found[0] if found else None


def launches(t0, t1, kinds=None):
    """The launches that began in [t0, t1), oldest first."""
    prof = profiler()
    if prof is None:
        return []
    return [r for r in prof.records()
            if t0 <= r.begin < t1 and (kinds is None or r.kind in kinds)]


def read(run, phase, kinds=None, stat="mean", scale=1000.0):
    if run.window is None:
        return None
    recs = launches(run.window.opened.at, run.window.closed.at, kinds)
    if phase == "gap":
        secs = [r.gap_secs for r in recs if r.gap_secs > 0.0]
    else:
        secs = [r.phase_secs(phase) for r in recs]
    return PICK[stat](secs) * scale if secs else None
