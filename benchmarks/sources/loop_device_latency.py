"""The engine's launch ring laid against the device trace of the
stretch after the window: what lies between the host's phases and the
device's operations.

The ring is on ``time.perf_counter``, the trace on the profiler's
clock.  They are joined with what ``run`` already holds: each entry of
``run.step_samples[name]`` carries a ``perf_counter`` stamp ``t`` taken
just before the benchmark's annotation ``name`` whose start on the
trace's clock is ``run.trace.annotations[name][i][0]``.  The offset is
the median of the differences over all annotations; it is refused (the
metric reads nothing) when the differences spread by more than 0.2 ms.
A profiler that stopped while a step was in flight records no
annotation for that last sample, so a list of samples longer than its
list of spans is laid against it at every shift and the tightest lay
over all annotations kept; spans without samples cannot be, and refuse.

For a launch, the device's operations that start between its
``dispatch`` start and its ``fetch`` end are that program's (the loop
is serial).  ``what``:

* ``launch``: first such operation's start minus ``dispatch`` start,
* ``fetch``: ``fetch`` end minus the last such operation's end,
* ``turnaround``: the next launch's ``dispatch`` start minus this
  ``fetch`` end (host clock alone; waits for work left out),
* ``idle_explained``: percent of the first device's idle seconds in the
  traced window that lie inside a launch's ``dispatch``..``fetch`` or in
  such a turnaround; the rest is time the spans do not cover.

The first three are the median (or mean) over the launches of ``kinds``
that lie wholly inside the traced window, in ``scale`` units (1000 =
ms)."""
import bisect
import importlib
import itertools
import statistics

_context = importlib.import_module("harness.context")
_trace = importlib.import_module("harness.trace")
_ring = importlib.import_module("harness.spec").load_module(
    "sources", "loop_phase")

MAX_SPREAD_S = 0.0002


def clock_offset(samples, spans):
    """``(offset, spread)``: trace clock minus host clock, from host
    stamps ``samples[name]`` (each a dict with ``t``) and trace spans
    ``spans[name]``; None when they cannot be laid against each other
    within ``MAX_SPREAD_S``."""
    ways = []           # per annotation, the differences at each shift
    for name, stamped in samples.items():
        host = sorted(s["t"] for s in stamped)
        traced = sorted(s for s, _ in spans.get(name, ()))
        extra = len(host) - len(traced)
        if extra < 0:
            return None
        if traced:
            ways.append([[a - h for a, h in zip(traced, host[shift:])]
                         for shift in range(extra + 1)])
    best = None
    for combo in itertools.product(*ways):
        diffs = [d for part in combo for d in part]
        if diffs and (best is None
                      or max(diffs) - min(diffs) < max(best) - min(best)):
            best = diffs
    if best is None or max(best) - min(best) > MAX_SPREAD_S:
        return None
    return statistics.median(best), max(best) - min(best)


def laid(run, kinds=None, cut=False):
    """The traced stretch's launches with their stamps moved to the
    trace's clock: ``[(record, dispatch start, fetch end)]``, oldest
    first; None when there is no trace, ring or offset.  The stretch is
    the trace's own window (the device's operations outside it were not
    recorded): a launch that a window edge cuts is left out, unless
    ``cut``."""
    if run.trace is None or not run.trace.devices:
        return None
    if "clock_offset_s" not in run.setup_parts:
        found = clock_offset(run.step_samples, run.trace.annotations)
        run.setup_parts["clock_offset_s"] = found and found[0]
        _context.note("clock_offset", joined=found is not None,
                      spread_ms=found and found[1] * 1e3,
                      limit_ms=MAX_SPREAD_S * 1e3)
    off = run.setup_parts["clock_offset_s"]
    if off is None:
        return None
    lo, hi = run.trace.window
    rows = []
    for r in _ring.launches(float("-inf"), hi - off, kinds):
        ds, fe = r.phase_start("dispatch") + off, r.phase_end("fetch") + off
        if (fe > lo and ds < hi) if cut else (ds >= lo and fe <= hi):
            rows.append((r, ds, fe))
    return rows


def device_edges(ops, lo, hi):
    """(first start, last end) of the operations that start in
    [lo, hi); ``ops`` sorted by start.  None when there is none."""
    i = bisect.bisect_left(ops, (lo,))
    j = bisect.bisect_left(ops, (hi,))
    if i >= j:
        return None
    return ops[i][0], max(e for _, e in ops[i:j])


def turnarounds(rows):
    """``(fetch end, next dispatch start)`` of consecutive launches;
    a pair the engine spent waiting for work in is left out."""
    return [(fe, nds) for (r, _, fe), (nr, nds, _) in zip(rows, rows[1:])
            if nr.seq == r.seq + 1 and nr.gap_secs > 0.0]


def read(run, what, kinds=None, stat="median", scale=1000.0):
    rows = laid(run, kinds, cut=what == "idle_explained")
    if not rows:
        return None
    device = run.trace.devices[0]
    if what == "idle_explained":
        idle = _trace.subtract([run.trace.window], device.busy())
        if not idle:
            return None
        covered = _trace.union([(ds, fe) for _, ds, fe in rows]
                               + turnarounds(rows))
        hidden = _trace.subtract(idle, covered)
        return 100.0 * (1.0 - _trace.total(hidden) / _trace.total(idle))
    if what == "turnaround":
        secs = [nds - fe for fe, nds in turnarounds(rows)]
    else:
        ops = sorted((s, e) for _, s, e in device.ops)
        secs = []
        for _, ds, fe in rows:
            edges = device_edges(ops, ds, fe)
            if edges is not None:
                secs.append(edges[0] - ds if what == "launch"
                            else fe - edges[1])
    return _ring.PICK[stat](secs) * scale if secs else None
