"""Device time by what an operation IS: every operation of the traced
stretch is looked up, by the name the trace prints for it, in the
instruction table of the compiled program that ran it, which the program
itself publishes (``megatron_llm_tpu/hlo_collectives.py::ProgramTable``:
opcode, shapes, operands, loops, ``op_name``, scope, role, and for a
collective the mesh axes its groups run over).

Whose table.  Serving: the engine's launch ring says which program ran
when.  ``loop_device_latency.laid`` puts the stretch's launches on the
trace's clock; an operation belongs to the launch whose ``dispatch``
start .. ``fetch`` end holds its start AND in one of whose programs'
tables its name is (``loop_profiler.LAUNCH_PROGRAMS``: a last prefill
chunk samples its first token in the same launch).  Where the name is
in none of them (skew between the clocks at an edge; a page program,
which runs while the next launch's inputs are built) the neighbouring
launches' tables are tried, then the page programs', and only then is
the operation unattributed: a millisecond of skew cannot move an
operation into another program.  The tables are built when this source
is first read (``LoopProfiler.program_tables()``: after the engine has
stopped, outside the window and ``setup_s``, in traced runs only); a
note ``op_roles`` says what that cost and how many operations needed a
neighbour.  Training: one program on every device and no ring; its
table is ``loop_profiler.live_programs()``'s.

A program that publishes no tables (the parent of the PR that brought
them) reads as nothing and the metric is left out.

``what``:

* ``busy_share``: seconds in the matching operations as a percentage of
  the device's busy seconds (the union of its operations; ``while`` /
  ``conditional`` / ``call`` enclose others and are left out of both);
* ``unattributed_share``: the same for the operations no table knows or
  that have no role, scope or edge;
* ``per_launch_ms``: median over the launches of ``kinds`` that lie
  wholly inside the stretch of the union of the launch's own operations
  (every role), in ``scale`` units (1000 = ms): the program's device
  time by the ring;
* ``exposed_share``: time inside the matching collectives during which
  no operation that is not a collective runs on that device, as a
  percentage of the traced window; the worst device.

A match is by ``role``, ``scope``, ``edge`` and ``opcode`` (each a name
or a list; ``opcode`` is held against a fusion's root too); what is not
given matches all."""
import bisect
import importlib
import statistics
import time

_context = importlib.import_module("harness.context")
_trace = importlib.import_module("harness.trace")
_spec = importlib.import_module("harness.spec")
_latency = _spec.load_module("sources", "loop_device_latency")
_ring = _spec.load_module("sources", "loop_phase")

_DONE = {}          # id(run.trace) -> what attribute() found


def published(run):
    """``(tables, launch programs, page programs)`` as the program
    publishes them: the serve loop's where a profiler with launches has
    any, else the registered programs'; None where it publishes none."""
    try:
        from megatron_llm_tpu.serving import loop_profiler
    except ImportError:
        return None
    prof = _ring.profiler()
    build = getattr(prof, "program_tables", None)
    if build is not None and prof.launches():
        tables = build()
        if tables:
            return (tables, loop_profiler.LAUNCH_PROGRAMS,
                    loop_profiler.PAGE_PROGRAMS)
    live = getattr(loop_profiler, "live_programs", None)
    tables = live() if live is not None else {}
    return (tables, None, ()) if tables else None


def _lookup(tables, programs, name):
    for program in programs:
        table = tables.get(program)
        row = table.get(name) if table is not None else None
        if row is not None:
            return program, row
    return None


def by_launch(ops, rows, tables, launch_programs, page_programs):
    """Each of ``ops`` ``(name, start, end)`` as ``(name, start, end,
    row, index into rows, how)``: ``how`` is ``own`` (the launch that
    holds its start), ``neighbour``, ``page`` or None (no table of a
    launch near it knows the name: no row, and the index of the launch
    that holds its start, if one does)."""
    starts = [ds for _, ds, _ in rows]
    out = []
    for name, s, e in sorted(ops, key=lambda op: op[1]):
        i = bisect.bisect_right(starts, s) - 1
        holds = i if i >= 0 and s <= rows[i][2] else None
        # the launch that holds it, then its neighbours, the nearer edge
        # first
        near = sorted((j for j in (i - 1, i, i + 1) if 0 <= j < len(rows)),
                      key=lambda j: max(rows[j][1] - s, s - rows[j][2], 0.0))
        found = None
        for j in near:
            hit = _lookup(tables, launch_programs.get(rows[j][0].kind, ()),
                          name)
            if hit is not None:
                found = (hit[1], j, "own" if j == holds else "neighbour")
                break
        if found is None:
            hit = _lookup(tables, page_programs, name)
            if hit is not None:
                # a page program runs while the next launch's inputs are
                # built
                j = min(i + 1, len(rows) - 1) if holds is None else holds
                found = (hit[1], j, "page")
        out.append((name, s, e) + (found or (None, holds, None)))
    return out


def attribute(run):
    """Per device of the trace, its operations with their rows:
    ``{"devices": [[(name, start, end, row, index, how)]], "rows":
    launches laid or None}``; None where the program publishes no
    table."""
    if run.trace is None or not run.trace.devices:
        return None
    key = id(run.trace)
    if key in _DONE:
        return _DONE[key]
    t0 = time.perf_counter()
    compiles = len(run.meter.events) if run.meter is not None else 0
    found = published(run)
    build_s = time.perf_counter() - t0
    result = None
    if found is not None:
        tables, launch_programs, page_programs = found
        if launch_programs is None:     # one program on every device
            result = {"rows": None, "devices": [
                [(n, s, e) + ((hit[1], None, "own") if hit else
                              (None, None, None))
                 for n, s, e in d.ops
                 for hit in [_lookup(tables, list(tables), n)]]
                for d in run.trace.devices]}
        else:
            rows = _latency.laid(run, cut=True)
            if rows:
                result = {"rows": rows, "devices": [by_launch(
                    run.trace.devices[0].ops, rows, tables,
                    launch_programs, page_programs)]}
        if result is not None:
            ops = [op for dev in result["devices"] for op in dev
                   if not _container(op[0])]
            how = [op[5] for op in ops]
            _context.note(
                "op_roles", programs=sorted(tables),
                instructions={n: len(t.rows) for n, t in tables.items()},
                build_s=build_s,
                backend_compiles=(len(run.meter.events) - compiles
                                  if run.meter is not None else None),
                operations=len(ops), neighbour=how.count("neighbour"),
                page=how.count("page"), in_no_table=how.count(None),
                largest_unattributed=largest_unattributed(ops),
                families=families(ops))
    _DONE.clear()
    _DONE[key] = result
    return result


def _container(name):
    return bool(_trace.CONTAINER.match(_trace.op_family(name)))


def _known(row):
    return row is not None and bool(row["role"] or row["edge"])


def largest_unattributed(ops, n=5):
    """The ``n`` instructions with the most seconds among those no table
    knows or that have no role: name, seconds, and of a known row its
    opcode (a fusion's root), shape and operands."""
    acc = {}
    for name, s, e, row, _, _ in ops:
        if not _container(name) and not _known(row):
            name = name.lstrip("%").split(" = ")[0]
            acc[name] = (acc.get(name, (0.0,))[0] + e - s, row)
    out = []
    for name, (secs, row) in sorted(acc.items(),
                                    key=lambda kv: -kv[1][0])[:n]:
        out.append({"name": name, "seconds": secs,
                    **({} if row is None else {
                        "opcode": row["root"], "dtype": row["dtype"],
                        "shape": list(row["shape"]),
                        "operands": row["operands"][:4],
                        "op_name": row["op_name"][-80:]})})
    return out


def families(ops, n=10, kinds=3):
    """What the ``n`` operation families with the most seconds ARE: for
    each its seconds and its ``kinds`` largest (program, role, a fusion's
    root, dtype and shape) with theirs."""
    acc = {}
    for name, s, e, row, _, _ in ops:
        what = ("in no table" if row is None else
                f"{row['program']} {row['role'] or '-'} {row['root']} "
                f"{row['dtype']}{list(row['shape'])}")
        fam = acc.setdefault(_trace.op_family(name), {})
        fam[what] = fam.get(what, 0.0) + e - s
    top = sorted(acc.items(), key=lambda kv: -sum(kv[1].values()))[:n]
    return {fam: {"seconds": sum(parts.values()),
                  "largest": sorted(parts.items(),
                                    key=lambda kv: -kv[1])[:kinds]}
            for fam, parts in top}


def _among(value, wanted):
    return wanted is None or value in (
        [wanted] if isinstance(wanted, str) else wanted)


def matches(row, role=None, scope=None, edge=None, opcode=None):
    if row is None:
        return False
    return (_among(row["role"], role) and _among(row["scope"], scope)
            and _among(row["edge"], edge)
            and (_among(row["opcode"], opcode)
                 or _among(row["root"], opcode)))


def _is_collective(name, row):
    if row is not None and ("family" in row or row["edge"]):
        return True
    return bool(_trace.COLLECTIVE.match(_trace.op_family(name)))


def read(run, what, role=None, scope=None, edge=None, opcode=None,
         kinds=None, scale=1000.0):
    found = attribute(run)
    if found is None:
        return None
    want = dict(role=role, scope=scope, edge=edge, opcode=opcode)
    devices = [[op for op in dev if not _container(op[0])]
               for dev in found["devices"]]
    if what in ("busy_share", "unattributed_share"):
        busy = sum(_trace.total(_trace.union([(s, e) for _, s, e, *_ in dev]))
                   for dev in devices)
        if not busy:
            return None
        if what == "busy_share":
            secs = sum(e - s for dev in devices
                       for _, s, e, row, _, _ in dev if matches(row, **want))
        else:
            secs = sum(e - s for dev in devices
                       for _, s, e, row, _, _ in dev if not _known(row))
        return 100.0 * secs / busy
    if what == "exposed_share":
        if not run.trace.window_s:
            return None
        worst = 0.0
        for dev in devices:
            coll = _trace.union([(s, e) for n, s, e, row, _, _ in dev
                                 if _is_collective(n, row)
                                 and matches(row, **want)])
            rest = _trace.union([(s, e) for n, s, e, row, _, _ in dev
                                 if not _is_collective(n, row)])
            worst = max(worst, _trace.total(_trace.subtract(coll, rest)))
        return 100.0 * worst / run.trace.window_s
    if what == "per_launch_ms":
        rows = found["rows"]
        if not rows:
            return None
        lo, hi = run.trace.window
        whole = {i for i, (r, ds, fe) in enumerate(rows)
                 if ds >= lo and fe <= hi
                 and (kinds is None or r.kind in kinds)}
        own = {}
        for _, s, e, row, i, _ in devices[0]:
            if i in whole:
                own.setdefault(i, []).append((s, e))
        secs = [_trace.total(_trace.union(ivs)) for ivs in own.values()]
        return statistics.median(secs) * scale if secs else None
    raise ValueError(f"op_role_time: no reading {what!r}")
