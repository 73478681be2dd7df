"""Where a request's time to first token went, from the engine's
request spans and launch ring: over the requests SUBMITTED inside the
counted window that got a first token, the percentile of

* ``prefill_own``: the summed ``dispatch`` + ``fetch`` of the request's
  own prefill launches up to its first token, or
* ``interleave``: first token minus admit minus the above: the time an
  admitted, prefilling request waited on other requests' launches and
  on the loop's host phases,

in ``scale`` units (1000 = ms)."""
import importlib

_w = importlib.import_module("harness.window")
_ring = importlib.import_module("harness.spec").load_module(
    "sources", "loop_phase")


def split(run):
    """(own, interleave) seconds of each such request."""
    prof = _ring.profiler()
    if prof is None or run.window is None:
        return []
    own = {}
    for r in prof.records():
        if r.kind == "prefill":
            own.setdefault(r.request, []).append(r)
    out = []
    for s in prof.request_spans():
        if (not run.window.contains(s.submit) or s.admit is None
                or s.first_token is None):
            continue
        mine = sum(r.wait_secs for r in own.get(s.request, ())
                   if r.phase_start("dispatch") < s.first_token)
        out.append((mine, s.first_token - s.admit - mine))
    return out


def read(run, what, q=50, scale=1000.0):
    column = {"prefill_own": 0, "interleave": 1}[what]
    p = _w.percentile([row[column] for row in split(run)], q)
    return None if p is None else p * scale
