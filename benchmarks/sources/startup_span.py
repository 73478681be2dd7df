"""Set-up's seconds by name, from the program's own start-up timeline
(``megatron_llm_tpu/tracing.py``: ``startup_timeline()``, the spans the
entry points open between their first stamp and "ready", and the compile
ledger's events) on the clock ``run.process_start`` and
``window_opened_at`` are on: ``time.perf_counter``, no offset.

``part``:

* ``imports``: the top-level span ``imports``,
* ``build``: the spans of ``BUILD`` at top level, each less the union of
  the ledger's events inside it (what was traced, lowered, compiled or
  loaded there is another part's),
* ``trace_lower`` / ``cache_load``: union seconds of the ledger's events
  of those kinds that ended before the window opened (a cache load lies
  INSIDE the backend event ``compile_s`` sums),
* ``warm_run``: ``warmup`` (serving) or ``first_step`` (training) less
  the ledger's union inside it: the first executions themselves,
* ``unnamed_pct``: of set-up (process start to the window's opening)
  less the lead-in, the share that lies in no top-level span and not
  before the program's first stamp.  The lead-in is the traffic file's
  (serving) or the warm steps after the first, "ready" to the window
  (training).  Reading it prints the note ``setup_timeline``.

A program that keeps no timeline (the parent of the PR that brought it)
reads as nothing and the metric is left out."""
import importlib

_context = importlib.import_module("harness.context")
_trace = importlib.import_module("harness.trace")

BUILD = ("initialize", "build_model", "init_params", "load_checkpoint",
         "shard_params", "engine_init", "build_optimizer", "build_data",
         "build_train_step")
WARM = ("warmup", "first_step")


def timeline():
    """(the timeline, every event of the ledger), or None where the
    program keeps none."""
    try:
        from megatron_llm_tpu import tracing
    except ImportError:
        return None
    get = getattr(tracing, "startup_timeline", None)
    found = get() if get is not None else None
    if not found:
        return None
    return found, list(tracing.compile_ledger().events)


def top_level(spans):
    """The spans that lie inside no other, by start."""
    return sorted(
        (a for a in spans
         if not any(b is not a and b[1] <= a[1] and a[2] <= b[2]
                    and b[2] - b[1] > a[2] - a[1] for b in spans)),
        key=lambda s: s[1])


def union_s(events, lo, hi, kinds=None):
    """Union seconds of the ledger's events (of ``kinds``) in [lo, hi]."""
    return _trace.clip(_trace.union(
        [(s, e) for kind, _, s, e, *_ in events
         if kinds is None or kind in kinds]), lo, hi)


def parts(tl, events, process_start, opened, lead_in_s=None):
    """Every part at once, and what the note prints."""
    top = [s for s in top_level(tl["spans"]) if s[1] < opened]
    lead = (lead_in_s if lead_in_s is not None
            else max(opened - tl["ready"], 0.0))
    whole = (opened - process_start) - lead
    before = tl["first"] - process_start
    named = before + sum(min(t1, opened) - t0 for _, t0, t1, _ in top)
    inside = {(n, t0): union_s(events, t0, t1) for n, t0, t1, _ in top}
    out = {
        "imports": sum(t1 - t0 for n, t0, t1, _ in top if n == "imports"),
        "build": sum(t1 - t0 - inside[n, t0] for n, t0, t1, _ in top
                     if n in BUILD),
        "trace_lower": union_s(events, float("-inf"), opened,
                               ("trace", "lower")),
        "cache_load": union_s(events, float("-inf"), opened,
                              ("cache_load",)),
        "backend": union_s(events, float("-inf"), opened, ("backend",)),
        "warm_run": sum(t1 - t0 - inside[n, t0] for n, t0, t1, _ in top
                        if n in WARM),
        "unnamed_pct": 100.0 * (whole - named) / whole if whole > 0 else None,
        "before_first_stamp": before, "lead_in": lead, "setup_less_lead": whole,
    }
    if not any(n in WARM for n, *_ in top):
        out["warm_run"] = None
    return out, top


def read(run, part):
    if run.setup_s is None:
        return None
    found = timeline()
    if found is None:
        return None
    tl, events = found
    opened = run.setup_parts["window_opened_at"]
    out, top = parts(tl, events, run.process_start, opened,
                     run.setup_parts.get("lead_in_s"))
    if part == "unnamed_pct":
        depth = {id(s): sum(1 for o in tl["spans"] if o is not s
                            and o[1] <= s[1] and s[2] <= o[2]
                            and o[2] - o[1] > s[2] - s[1])
                 for s in tl["spans"]}
        _context.note(
            "setup_timeline", setup_s=run.setup_s,
            before_first_stamp_s=out["before_first_stamp"],
            lead_in_s=out["lead_in"],
            ready_at_s=tl["ready"] - run.process_start,
            spans=[{"name": s[0], "at_s": s[1] - run.process_start,
                    "s": s[2] - s[1], "depth": depth[id(s)],
                    "ledger_union_s": union_s(events, s[1], s[2])}
                   for s in tl["spans"]],
            union_s={k: out[k] for k in ("trace_lower", "cache_load",
                                         "backend")},
            top_programs=tl["summary"]["top_programs"],
            unnamed_pct=out["unnamed_pct"])
    return out[part]
