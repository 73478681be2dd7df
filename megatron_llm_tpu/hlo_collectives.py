"""What a compiled program is made of, read from its own text.

``compiled.as_text()`` (optimised HLO, after the SPMD partitioner) names
every instruction with its opcode, its shapes, its operands, the
computation that holds it and the ``op_name`` the trace left on it; a
``while`` names its body and, where the compiler knows it, its trip count;
a collective names its replica groups.  ``instructions`` reads that text
into one row an instruction, under the name a profiler's trace prints for
it, so that a device operation can be looked up in the program that ran
it.  ``collectives`` is the rows that are collectives: family, device
groups, dtypes, bytes, the trip counts of the loops around it, and so the
calls it makes each time the program runs.  ``mesh_groups`` gives the
groups a mesh axis makes, to hold a row's groups against, and
``ProgramTable`` is the rows of one program with a ``role`` each, given
by whoever owns the shapes (the engine its pool's, the trainer its
mesh's).

``python -m megatron_llm_tpu.hlo_collectives step.hlo.txt`` prints the
collectives' table, with ``--instructions`` the instructions by role
and opcode (docs/guide/collective_placement.md,
docs/guide/observability.md).  Standard library only.
"""

from __future__ import annotations

import itertools
import json
import math
import re
import sys
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Tuple

FAMILIES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
            "collective-permute")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
             "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s"
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


# the named scopes the programs' sources open (``jax.named_scope``), which
# arrive in an instruction's ``op_name`` as path components; a row's
# ``scope`` is the innermost of these its ``op_name`` holds
SCOPES = ("sampler", "kv_write", "moe_route", "moe_dispatch", "moe_experts",
          "moe_combine", "moe_shared", "qk_norm", "dsa_indexer",
          "mla_absorb", "mla_expand", "mla_query_down", "mla_query_up",
          "ssm_in_proj", "ssm_conv", "ssm_scan",
          "ssm_step", "ssm_gate_norm", "ssm_out_proj", "mamba",
          "conv_in_proj", "short_conv", "conv_out_proj",
          "retention_gate", "retention_chunk", "retention_step",
          "delta_proj", "delta_conv", "delta_gate", "delta_chunk",
          "delta_step", "delta_norm",
          "attn_gate", "post_attn_norm", "post_mlp_norm",
          "attention", "mlp",
          "embedding", "lm_head", "transformer_layer",
          "loop_pass_norm", "loop_pass")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_SCOPE_CORE = re.compile(r"^(?:\w+\()*([\w.\-]+)\)*$")
_NAME = re.compile(r"^[A-Za-z_][\w.\-]*$")
# an instruction that moves its operand and computes nothing
COPIES = ("copy", "copy-done", "slice-done")
_POOL_MOVES = COPIES + ("copy-start", "slice-start", "dynamic-update-slice")


def scope_of(op_name: str, scopes: Sequence[str] = SCOPES) -> str:
    """The innermost of ``scopes`` among the path components of an
    ``op_name`` (``jit(step)/transpose(jvp(attention))/mul`` is in
    ``attention``); ``""`` where it holds none."""
    for part in reversed(op_name.split("/")):
        m = _SCOPE_CORE.match(part)
        if m and m.group(1) in scopes:
            return m.group(1)
    return ""


def _operands(line: str, at: int) -> List[str]:
    """Names between the parenthesis at ``line[at]`` and its partner."""
    depth, j = 0, at
    for j in range(at, len(line)):
        depth += {"(": 1, ")": -1}.get(line[j], 0)
        if depth == 0:
            break
    inside = line[at + 1:j]
    if "%" in inside:
        return re.findall(r"%([\w.\-]+)", inside)
    last = [part.split()[-1] for part in inside.split(",") if part.strip()]
    return [t for t in last if _NAME.match(t)]


def _nbytes(shapes) -> int:
    return sum(_ITEMSIZE.get(d, 4) * math.prod(dims) for d, dims in shapes)


def _parse(line: str, scopes: Sequence[str]) -> Optional[dict]:
    m = _INSTR.match(line)
    if not m:
        return None
    shapes = [(d, tuple(_ints(dims)))
              for d, dims in _SHAPE.findall(m.group(3))]
    op = _OP_NAME.search(line)
    op_name = op.group(1).replace("\\'", "'") if op else ""
    return {
        "name": m.group(2), "opcode": m.group(4), "is_root": bool(m.group(1)),
        "dtype": shapes[0][0] if shapes else "",
        "shape": shapes[0][1] if shapes else (),
        "shapes": shapes, "bytes": _nbytes(shapes),
        "operands": _operands(line, m.end() - 1),
        "op_name": op_name, "scope": scope_of(op_name, scopes),
    }


def _fused_scope(body: List[dict], own: str) -> str:
    """A fusion's scope: the one that most of its body's instructions
    carry, among those that carry one (the compiler's own converts and
    broadcasts carry no ``op_name``, and what the source left outside
    every scope, a residual add fused into the matmul before it, carries
    none either: neither votes); the ROOT's where two scopes tie; the
    fusion's own ``op_name``'s where no body instruction carries one."""
    votes: Dict[str, int] = {}
    for r in body:
        if r["scope"]:
            votes[r["scope"]] = votes.get(r["scope"], 0) + 1
    if not votes:
        return own
    best = max(votes.values())
    leaders = [s for s, n in votes.items() if n == best]
    root = next((r["scope"] for r in body if r["is_root"]), own)
    return root if root in leaders else sorted(leaders)[0]


def instructions(hlo_text: str,
                 scopes: Sequence[str] = SCOPES) -> List[dict]:
    """One row an instruction of a compiled module's text, in the text's
    order: ``name`` (as a profiler's trace prints it, without ``%``),
    ``opcode``, the result's ``dtype`` / ``shape`` (a tuple's first) with
    every result in ``shapes`` and their ``bytes`` together, ``operands``
    (names), ``computation``, ``under`` (the opcodes of the call sites
    around its computation, outermost first), ``loops`` (trip counts of
    the enclosing ``while`` bodies, outermost first; None for a count the
    compiler does not state), ``calls`` a run, ``op_name`` (the metadata
    string, ``""`` where the compiler made the instruction itself) and
    ``scope`` (``scope_of`` it).  A collective's row has ``family``,
    ``groups`` and ``dtypes`` besides.

    An instruction inside a fusion's body is no row (a trace has no event
    for it, nor for a reducer's ``to_apply``); the fusion's row carries
    ``root`` (its body's ROOT opcode; any other row's own opcode) and the
    scope ``_fused_scope`` gives it: the scope most of its body's scoped
    instructions carry, the ROOT's on a tie.  A loaded executable prints
    an asynchronous slice or copy as ``async-start`` / ``async-done``
    around a computation that holds the one instruction: such a row's
    ``root`` is that instruction's opcode with ``-start`` / ``-done``
    (``slice-done``), as the compiler's own name for it reads, and the
    ``async-start`` says in ``wraps`` which instruction it is."""
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

    def body_of(comp):
        return [r for r in (_parse(ln, scopes) for ln in comps.get(comp, ()))
                if r is not None]

    # computation -> (trip counts, opcodes) of the call sites around its
    # (first) call site; a fusion's body and a reducer are not walked
    around: Dict[str, Tuple[Tuple, Tuple]] = {entry: ((), ())}
    parsed: Dict[str, List[dict]] = {}
    todo = [entry]
    while todo:
        comp = todo.pop()
        parsed[comp] = rows = []
        for line in comps.get(comp, ()):
            row = _parse(line, scopes)
            if row is None:
                continue
            row["_line"] = line
            rows.append(row)
            called = _CALLED.findall(line)
            b = _BRANCHES.search(line)
            if b:
                called += [c.strip().lstrip("%")
                           for c in b.group(1).split(",")]
            if not called:
                continue
            op = row["opcode"]
            if op == "fusion":
                body = body_of(called[0])
                row["root"] = next((r["opcode"] for r in body
                                    if r["is_root"]), op)
                row["scope"] = _fused_scope(body, row["scope"])
                continue
            if op == "async-start":
                inner = next((r for r in body_of(called[0])
                              if r["is_root"]), None)
                if inner is not None:
                    row["root"] = inner["opcode"] + "-start"
                    row["wraps"] = inner["name"]
            loops, under = around[comp]
            body = re.search(r"\bbody=%?([\w.\-]+)", line)
            applied = re.search(r"\bto_apply=%?([\w.\-]+)", line)
            for c in called:
                if c in around or c not in comps:
                    continue
                if applied and c == applied.group(1) and op != "call":
                    continue
                if body and c == body.group(1):
                    around[c] = (loops + (_trips(line, comps),),
                                 under + (op,))
                else:
                    around[c] = (loops, under + (op,))
                todo.append(c)

    out = []
    for comp, lines in comps.items():       # the text's order
        for row in parsed.get(comp, ()):
            loops, under = around[comp]
            row.update(computation=comp, loops=loops, under=under,
                       calls=math.prod(t or 1 for t in loops))
            row.setdefault("root", row["opcode"])
            del row["is_root"]
            line = row.pop("_line")
            op = row["opcode"]
            family = op[:-len("-start")] if op.endswith("-start") else op
            if family in FAMILIES:
                shapes = row["shapes"]
                if op == "all-gather-start" and len(shapes) > 1:
                    shapes = shapes[len(shapes) // 2:]  # (operands, results)
                row.update(
                    family=family, groups=_groups(line),
                    dtypes=sorted({d for d, _ in shapes}),
                    bytes=_nbytes(shapes))
            out.append(row)
    by_name = {r["name"]: r for r in out}
    for row in out:         # an asynchronous pair's second half
        if row["opcode"] in ("async-done", "async-update"):
            start = by_name.get(row["operands"][0] if row["operands"]
                                else "", {})
            while start.get("opcode") == "async-update":
                start = by_name.get(start["operands"][0], {})
            if start.get("root", "").endswith("-start"):
                row["root"] = start["root"][:-len("start")] + "done"
    return out


def collectives(hlo_text: str) -> List[dict]:
    """One row a collective instruction of a compiled module's text (the
    rows of ``instructions`` that have a ``family``): ``family``,
    ``groups``, ``dtypes``, ``bytes`` (of its result: what an all-reduce
    moves in, an all-gather out), ``computation``, ``loops`` and ``calls``
    a run."""
    return [r for r in instructions(hlo_text) if "family" in r]


_HLO_DTYPE = {"bool": "pred", "int8": "s8", "uint8": "u8", "int16": "s16",
              "uint16": "u16", "int32": "s32", "uint32": "u32",
              "int64": "s64", "uint64": "u64", "float16": "f16",
              "bfloat16": "bf16", "float32": "f32", "float64": "f64",
              "float8_e4m3fn": "f8e4m3fn", "float8_e5m2": "f8e5m2"}


def edge_of(groups: Groups, mesh_shape: Dict[str, int]) -> str:
    """The mesh axes a collective's ``groups`` run over, joined by ``+``
    in the mesh's order: the fewest axes whose ``mesh_groups`` hold every
    group whole (a permute's pairs lie inside its ring's groups); no
    groups stated is every device, hence every axis; ``""`` where each
    group is one device."""
    names = [a for a in mesh_shape if mesh_shape[a] > 1]
    if not groups:
        return "+".join(names)
    for n in range(len(names) + 1):
        for axes in itertools.combinations(names, n):
            held = mesh_groups(mesh_shape, axes)
            if all(any(set(g) <= set(h) for h in held) for g in groups):
                return "+".join(axes)
    return ""


class ProgramTable:
    """The rows of one compiled program (``instructions`` of its text),
    each with its ``program``'s name, and a ``role`` and an ``edge`` from
    whoever owns the shapes:

    * ``kv_pool``: an instruction that only moves data (a copy, an
      asynchronous copy's or slice's halves, a dynamic-update-slice, or a
      fusion with one of these at its root) and whose result (of an
      asynchronous slice: whose operand, which the ``-start``'s result
      keeps) has exactly the dtype and shape of one of ``pool``'s arrays
      (``(numpy dtype name, shape)`` each): decided from the text and the
      shapes, never from a name;
    * ``ssm_state``: the same of one of ``state``'s arrays (a
      state-space layer's arrays a slot, which are no pages);
    * else its ``scope`` (``""`` where it has none);
    * ``edge``: for a collective, ``edge_of`` its groups on ``mesh_shape``
      (an ordered ``{axis: size}``), so that an all-reduce the compiler
      named ``psum.7`` is an all-reduce over ``dp`` because its replica
      groups say so (a ``-done`` has its ``-start``'s); ``""`` for any
      other row, and with no mesh."""

    def __init__(self, name: str, rows: List[dict],
                 pool: Iterable[Tuple[str, Tuple[int, ...]]] = (),
                 mesh_shape: Optional[Dict[str, int]] = None,
                 state: Iterable[Tuple[str, Tuple[int, ...]]] = ()):
        self.name, self.rows = name, rows
        self._by_name = {r["name"]: r for r in rows}
        pool = frozenset((_HLO_DTYPE.get(d, d), tuple(sh)) for d, sh in pool)
        state = frozenset((_HLO_DTYPE.get(d, d), tuple(sh))
                          for d, sh in state)
        for r in rows:
            r["program"] = name
            r["edge"] = (edge_of(r["groups"], mesh_shape)
                         if mesh_shape and "family" in r else "")
            r["role"] = ("kv_pool" if pool and self._moves(r, pool)
                         else "ssm_state" if state and self._moves(r, state)
                         else r["scope"])
        for r in rows:      # an asynchronous collective's two halves
            if "wraps" in r:
                r["edge"] = self._by_name.get(r["wraps"], r)["edge"]
        for r in rows:
            if r["opcode"].endswith(("-done", "-update")) and r["operands"]:
                r["edge"] = self._by_name.get(r["operands"][0],
                                              r).get("edge", "")

    def _moves(self, row: dict, pool: frozenset) -> bool:
        if row["root"] not in _POOL_MOVES:
            return False
        seen = list(row["shapes"])
        if row["opcode"].endswith("-done") and row["operands"]:
            # the piece an asynchronous slice takes is the pool's because
            # its -start's operand (kept in the -start's result) is
            seen += self._by_name.get(row["operands"][0], row)["shapes"]
        return any(s in pool for s in seen)

    def get(self, name: str) -> Optional[dict]:
        """The row of the instruction a trace's event names
        (``%copy.12``, with or without the ``%``)."""
        return self._by_name.get(name.lstrip("%").split(" = ")[0])

    def kv_pool_copy_bytes(self) -> int:
        """Bytes the program's ``kv_pool`` copies move each time it runs
        (an asynchronous pair counted at its ``-done``)."""
        return sum(r["bytes"] * r["calls"] for r in self.rows
                   if r["role"] == "kv_pool" and r["root"] in COPIES)

    def collectives_by_edge(self) -> Dict[str, Dict[str, int]]:
        """``{edge: {family: {calls, bytes}}}`` a run of the program."""
        out: Dict[str, Dict[str, Dict[str, int]]] = {}
        for r in self.rows:
            if "family" in r:
                m = out.setdefault(r["edge"], {}).setdefault(
                    r["family"], {"calls": 0, "bytes": 0})
                m["calls"] += r["calls"]
                m["bytes"] += r["bytes"] * r["calls"]
        return out

    def summary(self) -> Dict[str, object]:
        """What an operator reads of a program (``stats()['programs']``):
        its instructions, the bytes its pool copies move a launch, and
        the named scopes with the instructions each holds."""
        scopes: Dict[str, int] = {}
        for r in self.rows:
            if r["scope"]:
                scopes[r["scope"]] = scopes.get(r["scope"], 0) + 1
        return {"instructions": len(self.rows),
                "kv_pool_copy_bytes_per_launch": self.kv_pool_copy_bytes(),
                "scopes": dict(sorted(scopes.items()))}

    def table(self) -> str:
        """The rows grouped by (role, edge, opcode), most bytes first."""
        merged: Dict[tuple, dict] = {}
        for r in self.rows:
            if r["opcode"] in ("parameter", "constant", "tuple",
                               "get-tuple-element", "bitcast"):
                continue
            m = merged.setdefault((r["role"], r["edge"], r["root"]),
                                  {"n": 0, "per_run": 0})
            m["n"] += 1
            m["per_run"] += r["bytes"] * r["calls"]
        out = [f"{self.name}: role | edge | opcode (a fusion's root) | "
               "instructions | MB a run (results)"]
        for (role, edge, op), m in sorted(merged.items(),
                                          key=lambda kv: -kv[1]["per_run"]):
            out.append(" | ".join([role or "-", edge or "-", op, str(m["n"]),
                                   f"{m['per_run'] / 1e6:.2f}"]))
        return "\n".join(out)


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
    with open(sys.argv[-1]) as f:
        text = f.read()
    if "--instructions" in sys.argv[1:-1]:
        print(ProgramTable(sys.argv[-1], instructions(text)).table())
    else:
        print(table(collectives(text)))
