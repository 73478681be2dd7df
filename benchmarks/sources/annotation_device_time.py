"""Device time under a host annotation, from the trace: for each span of
that name the busy time of the busiest device inside it; the median (or
mean) over the spans that lie wholly inside the trace, in ``scale`` units."""
import statistics


def spans(run, annotation):
    if run.trace is None:
        return []
    secs = run.trace.under_annotation(annotation)
    # the first and last span may be cut by the trace's edges
    return secs[1:-1] if len(secs) > 4 else secs


def read(run, annotation, stat="median", scale=1000.0):
    secs = [s for s in spans(run, annotation) if s > 0]
    if not secs:
        return None
    pick = {"median": statistics.median, "mean": statistics.fmean}[stat]
    return pick(secs) * scale
