"""Where the collectives of a compiled program sit.

``compiled.as_text()`` (optimised HLO, after the SPMD partitioner) names
every collective with its replica groups, its shapes and the computation
that holds it; a ``while`` names its body and, where the compiler knows it,
its trip count.  ``collectives`` reads that text into one row a collective:
family, device groups, dtypes, bytes, the trip counts of the loops around
it, and so the calls it makes each time the program runs.  ``mesh_groups``
gives the groups a mesh axis makes, to hold a row's groups against.

``python -m megatron_llm_tpu.hlo_collectives step.hlo.txt`` prints the
table (docs/guide/collective_placement.md).  Standard library only.
"""

from __future__ import annotations

import itertools
import json
import math
import re
import sys
from typing import Dict, FrozenSet, List, Sequence, Tuple

FAMILIES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
            "collective-permute")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
             "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*?)\s"
                    r"([a-z][a-z\-]*)\(")
_SHAPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_CALLED = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_GROUPS_LIST = re.compile(r"replica_groups=\{((?:\{[0-9,]*\},?)*)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\]"
                          r"(?:T\(([0-9,]+)\))?")
_PAIRS = re.compile(r"source_target_pairs=\{((?:\{[0-9,]*\},?)*)\}")

_LIMIT = re.compile(r"%?([\w.\-]+)\s*=\s*s32\[\]\S*\s+constant\((\d+)\)")
_LESS = re.compile(r"ROOT.*\scompare\((.*?)\), direction=LT")

Groups = FrozenSet[Tuple[int, ...]]


def _ints(text: str) -> List[int]:
    return [int(t) for t in text.split(",") if t]


def _iota_groups(dims, reshape, perm) -> List[List[int]]:
    """``[g,s]<=[d0,d1,..]T(perm)``: arange(prod d) reshaped to ``d``,
    transposed by ``perm``, read out in rows of ``s``."""
    perm = perm or list(range(len(reshape)))
    strides = [math.prod(reshape[i + 1:]) for i in range(len(reshape))]
    flat = [sum(i * strides[p] for i, p in zip(idx, perm))
            for idx in itertools.product(*(range(reshape[p]) for p in perm))]
    size = dims[-1]
    return [flat[i:i + size] for i in range(0, len(flat), size)]


def _groups(line: str) -> Groups:
    m = _GROUPS_IOTA.search(line)
    if m:
        rows = _iota_groups(_ints(m.group(1)), _ints(m.group(2)),
                            _ints(m.group(3) or ""))
    else:
        m = _GROUPS_LIST.search(line) or _PAIRS.search(line)
        rows = [_ints(g) for g in re.findall(r"\{([0-9,]*)\}",
                                             m.group(1))] if m else []
    return frozenset(tuple(sorted(r)) for r in rows)


def _trips(while_line: str, comps: Dict[str, List[str]]):
    """A ``while``'s trip count: the compiler's own where it states one,
    else the bound of a condition that is ``counter < constant`` (what a
    ``lax.scan`` lowers to, counting from 0); None where neither reads."""
    m = _TRIPS.search(while_line)
    if m:
        return int(m.group(1))
    cond = re.search(r"\bcondition=%?([\w.\-]+)", while_line)
    lines = comps.get(cond.group(1), ()) if cond else ()
    limits = dict(mm.groups() for mm in map(_LIMIT.search, lines) if mm)
    less = next((mm for mm in map(_LESS.search, lines) if mm), None)
    if less is None:
        return None
    bounds = [int(limits[o.strip().lstrip("%")])
              for o in less.group(1).split(",")
              if o.strip().lstrip("%") in limits]
    return bounds[0] if len(bounds) == 1 else None


def mesh_groups(mesh_shape: Dict[str, int], axes: Sequence[str]) -> Groups:
    """The device groups that differ only along ``axes`` of a mesh whose
    devices are numbered in row-major order of ``mesh_shape`` (an ordered
    ``{axis: size}``, ``dict(mesh.shape)``), which is how a jitted program
    numbers them in its replica groups."""
    names = list(mesh_shape)
    sizes = [mesh_shape[a] for a in names]
    strides = [math.prod(sizes[i + 1:]) for i in range(len(sizes))]
    groups: Dict[Tuple[int, ...], List[int]] = {}
    for dev in range(math.prod(sizes)):
        coord = [(dev // st) % sz for st, sz in zip(strides, sizes)]
        rest = tuple(c for a, c in zip(names, coord) if a not in axes)
        groups.setdefault(rest, []).append(dev)
    return frozenset(tuple(g) for g in groups.values())


def collectives(hlo_text: str) -> List[dict]:
    """One row a collective instruction of a compiled module's text:
    ``family``, ``groups``, ``dtypes``, ``bytes`` (of its result: what an
    all-reduce moves in, an all-gather out), ``computation``, ``loops``
    (trip counts of the enclosing ``while`` bodies, outermost first; None
    for a count the compiler does not state) and ``calls`` a run."""
    comps: Dict[str, List[str]] = {}
    entry = name = None
    for line in hlo_text.splitlines():
        m = _HEADER.match(line.strip())
        if m:
            name = m.group(2)
            comps[name] = []
            if m.group(1):
                entry = name
        elif name is not None and "=" in line:
            comps[name].append(line)

    # computation -> the loops around its (first) call site
    loops: Dict[str, Tuple] = {entry: ()}
    todo = [entry]
    while todo:
        comp = todo.pop()
        for line in comps.get(comp, ()):
            called = _CALLED.findall(line)
            b = _BRANCHES.search(line)
            if b:
                called += [c.strip().lstrip("%")
                           for c in b.group(1).split(",")]
            if not called:
                continue
            inner = loops[comp]
            body = re.search(r"\bbody=%?([\w.\-]+)", line)
            for c in called:
                if c in loops or c not in comps:
                    continue
                if body and c == body.group(1):
                    loops[c] = inner + (_trips(line, comps),)
                else:
                    loops[c] = inner
                todo.append(c)

    rows = []
    for comp, lines in comps.items():
        if comp not in loops:
            continue
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            op = m.group(2)
            family = op[:-len("-start")] if op.endswith("-start") else op
            if family not in FAMILIES:
                continue
            shapes = _SHAPE.findall(m.group(1))
            if op == "all-gather-start" and len(shapes) > 1:
                shapes = shapes[len(shapes) // 2:]   # (operands, results)
            rows.append({
                "family": family,
                "groups": _groups(line),
                "dtypes": sorted({d for d, _ in shapes}),
                "bytes": sum(_ITEMSIZE.get(d, 4) * math.prod(_ints(dims))
                             for d, dims in shapes),
                "computation": comp,
                "loops": loops[comp],
                "calls": math.prod(t or 1 for t in loops[comp]),
            })
    return rows


def reductions_over(rows: List[dict], groups: Groups,
                    min_bytes: int = 1024) -> List[dict]:
    """The all-reduces and reduce-scatters over ``groups`` that carry more
    than a few scalars."""
    return [r for r in rows
            if r["family"] in ("all-reduce", "reduce-scatter")
            and r["groups"] == groups and r["bytes"] >= min_bytes]


def table(rows: List[dict]) -> str:
    """The rows grouped by (family, groups, dtypes, loops), largest first."""
    merged: Dict[tuple, dict] = {}
    for r in rows:
        key = (r["family"], tuple(sorted(r["groups"])), tuple(r["dtypes"]),
               r["loops"])
        m = merged.setdefault(key, {"n": 0, "bytes": 0, "per_run": 0})
        m["n"] += 1
        m["bytes"] += r["bytes"]
        m["per_run"] += r["bytes"] * r["calls"]
    out = ["family | groups | dtypes | enclosing loops (trips) | "
           "instructions | MB each pass | MB a run"]
    for key, m in sorted(merged.items(), key=lambda kv: -kv[1]["per_run"]):
        family, groups, dtypes, loops = key
        out.append(" | ".join([
            family, json.dumps(groups, separators=(",", ":")),
            ",".join(dtypes), "x".join(str(t or "?") for t in loops) or "-",
            str(m["n"]), f"{m['bytes'] / 1e6:.2f}",
            f"{m['per_run'] / 1e6:.2f}"]))
    return "\n".join(out)


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        print(table(collectives(f.read())))
