"""What a launch of the serve loop lost, and to what, from the engine's
own span ring (``megatron_llm_tpu/serving/loop_profiler.py``; the ring
``loop_phase.py`` reads): each record's ``compile_secs`` (union seconds
of the compile ledger's events that ended inside it on its thread) and
``gc_secs`` (seconds in garbage collections that ended inside it).

``what``:

* ``compile_s``: sum of ``compile_secs`` over the launches that began
  from the window's opening to the traced stretch's end (the window's
  end in an untraced run); 0.0 in a sound run,
* ``gc_ms``: mean ``gc_secs`` a launch, x1000, over the launches that
  began in the counted window,
* ``stall_pct``: over the launches that began in the counted window or
  in the stretch, the seconds by which a launch's ``dispatch`` +
  ``fetch`` exceeds ``FACTOR`` times its kind's median there, summed,
  over the seconds of both spans.  Reading it prints the note
  ``launch_stalls`` with the five launches that exceed it by most.

On a program whose records lack the two fields (the parent of the PR that
brought them) every one reads as nothing and the metric is left out."""
import importlib
import statistics

_context = importlib.import_module("harness.context")
_trace = importlib.import_module("harness.trace")
_spec = importlib.import_module("harness.spec")
_ring = _spec.load_module("sources", "loop_phase")

FACTOR = 3.0
PHASES = ("schedule", "draft", "build_inputs", "dispatch", "fetch", "emit")


def excess(records, factor=FACTOR):
    """``[(seconds over factor x its kind's median, record)]`` of the
    records that exceed it, largest first."""
    by_kind = {}
    for r in records:
        by_kind.setdefault(r.kind, []).append(r.wait_secs)
    medians = {k: statistics.median(v) for k, v in by_kind.items()}
    over = [(r.wait_secs - factor * medians[r.kind], r) for r in records]
    return sorted(((x, r) for x, r in over if x > 0.0),
                  key=lambda xr: -xr[0]), medians


def device_busy_inside(run, seqs):
    """Device-busy seconds inside each of the launches ``seqs`` that the
    trace covers (ring laid against the trace by
    ``loop_device_latency``), by ``seq``."""
    try:
        rows = _spec.load_module("sources", "loop_device_latency").laid(
            run, cut=True)
    except Exception:   # noqa: BLE001 - the note does without
        rows = None
    if not rows:
        return {}
    busy = run.trace.devices[0].busy()
    return {r.seq: _trace.clip(busy, ds, fe) for r, ds, fe in rows
            if r.seq in seqs}


def read(run, what):
    if run.window is None:
        return None
    t0, t1 = run.window.opened.at, run.window.closed.at
    traced = run.setup_parts.get("traced")
    stretch = traced if traced and traced[1] is not None else None
    counted = _ring.launches(t0, t1)
    if not counted or any(getattr(r, "gc_secs", None) is None
                          or getattr(r, "compile_secs", None) is None
                          for r in counted):
        return None
    if what == "gc_ms":
        return 1000.0 * sum(r.gc_secs for r in counted) / len(counted)
    if what == "compile_s":
        upto = stretch[1] if stretch else t1
        return float(sum(r.compile_secs for r in _ring.launches(t0, upto)))
    if what != "stall_pct":
        raise ValueError(what)
    recs, seconds = list(counted), t1 - t0
    if stretch:
        recs += _ring.launches(max(stretch[0], t1), stretch[1])
        seconds += stretch[1] - stretch[0]
    over, medians = excess(recs)
    worst = over[:5]
    busy = device_busy_inside(run, {r.seq for _, r in worst})
    _context.note(
        "launch_stalls", launches=len(recs), over=len(over),
        factor=FACTOR,
        median_wait_ms={k: v * 1e3 for k, v in medians.items()},
        worst=[{"seq": r.seq, "kind": r.kind, "over_ms": x * 1e3,
                "at_s": r.begin - t0,
                **{p + "_ms": r.phase_secs(p) * 1e3 for p in PHASES},
                "gap_ms": r.gap_secs * 1e3,
                "compile_secs": r.compile_secs, "gc_secs": r.gc_secs,
                **({"chunk_start": r.start} if r.kind == "prefill"
                   else {"rows": r.rows}),
                "device_busy_s": busy.get(r.seq)} for x, r in worst])
    return 100.0 * sum(x for x, _ in over) / seconds if seconds > 0 else None
