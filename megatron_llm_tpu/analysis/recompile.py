"""Checker ``recompile``: host-sync / retrace hazards in jitted code.

The serving engine's zero-steady-state-recompile guarantee (and the
training step's compile-once discipline) dies by a thousand cuts:
one ``.item()`` in a helper three calls below ``_decode_impl``, one
``if`` on a traced value, one ``self.config.x`` read resolved at trace
time instead of once at ``__init__``.  Runtime guard tests catch the
recompile *after* it happens on a hot path; this checker catches the
hazard in review.

Mechanics: find every ``jax.jit`` / ``shard_map`` / ``pallas_call``
root (call sites, decorators, ``partial(jax.jit, ...)``), resolve the
traced callables (module functions, ``self._method``, nested defs,
lambdas, plus callables handed to ``lax.scan``-family combinators),
walk the intra-package call graph from those roots, and flag inside
every reachable function:

* ``RC001`` — ``.item()`` (device sync, blocks the dispatch pipeline)
* ``RC002`` — ``float()``/``int()``/``bool()`` on a traced parameter
* ``RC003`` — ``np.asarray``/``np.array`` on a traced parameter
  (silent device→host transfer + constant-folding retrace hazard)
* ``RC004`` — ``if``/``while`` branching on a traced parameter
  (``is None``, ``.shape``/``.ndim``/``.dtype``, ``len()`` and
  ``isinstance()`` tests are static and exempt)
* ``RC005`` — reading ``self.config.*`` / ``self.cfg.*`` /
  ``self.args.*`` inside a jit-reachable method: mutable config must
  be resolved ONCE at ``__init__`` into frozen attributes (the engine's
  ``paged_kernel``/``prefill_kernel`` pattern), or every config change —
  and every dict-ordering accident — is a retrace.

Parameters are treated as *static* (not traced) when they are ``self``/
``cls``, a known config/mode name, annotated with a python scalar type
or a ``*Config`` dataclass, or defaulted to a bool/str constant —
that is how this codebase spells "static argument" by convention.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from megatron_llm_tpu.analysis.core import (
    Repo, Violation, dotted_name,
    Scope as _Scope, ModuleIndex as _Module, PackageIndex,
    resolve_callable, enclosing_scope as _enclosing_scope,
)

CHECKER = "recompile"

#: parameter names that are static-by-convention in this codebase
STATIC_PARAM_NAMES = frozenset((
    "self", "cls", "cfg", "config", "mcfg", "tcfg", "pcfg", "train_cfg",
    "parallel_cfg", "args", "mesh", "topology", "axis", "axis_name",
    "name", "mode", "dtype", "train", "deterministic", "interpret",
    "block_q", "block_k", "num_stages", "schedule",
))

#: static annotation spellings (python scalars + config dataclasses)
_STATIC_ANNOTATIONS = frozenset(("bool", "str", "int", "float"))

#: call suffixes that trace their callable arguments
_TRACING_COMBINATORS = frozenset((
    "scan", "while_loop", "cond", "fori_loop", "switch", "map",
    "vmap", "grad", "value_and_grad", "checkpoint", "remat",
    "custom_vjp", "custom_jvp", "named_call",
))

_NP_ROOTS = frozenset(("np", "numpy", "onp"))
_NP_HOST_CALLS = frozenset(("asarray", "array", "copy", "frombuffer"))
_SHAPE_ATTRS = frozenset(("shape", "ndim", "dtype", "size"))
#: attribute probes that are static inside a branch test: metadata
#: (shape/dtype) and pytree-structure lookups (`params.get("bias")`)
_STATIC_TEST_ATTRS = _SHAPE_ATTRS | frozenset(
    ("get", "keys", "values", "items"))


def _is_jit_expr(node: ast.AST) -> bool:
    """Does this expression denote jax.jit/pjit itself?"""
    d = dotted_name(node)
    if d is None:
        return False
    return d in ("jax.jit", "jit", "pjit", "jax.pjit") or d.endswith(".pjit")


def _root_kind(func_expr: ast.AST) -> Optional[str]:
    """'jit' | 'shard_map' | 'pallas' for a Call's func expr, else None."""
    d = dotted_name(func_expr)
    if d is None:
        return None
    if _is_jit_expr(func_expr):
        return "jit"
    last = d.rsplit(".", 1)[-1]
    if last == "shard_map":
        return "shard_map"
    if last == "pallas_call":
        return "pallas"
    return None


#: call-graph machinery lives in core.py (shared with the ``threads``
#: checker); kept under the old local names for this module's walkers.
_Index = PackageIndex
_resolve_callable = resolve_callable


def _find_roots(index: _Index) -> List[Tuple[_Module, ast.AST]]:
    """Every function def traced by jit/shard_map/pallas_call."""
    roots: List[Tuple[_Module, ast.AST]] = []
    for mod in index.by_path.values():
        # decorators: @jax.jit, @partial(jax.jit, ...)
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _is_jit_expr(dec):
                        roots.append((mod, node))
                    elif isinstance(dec, ast.Call):
                        dd = dotted_name(dec.func)
                        if dd and dd.rsplit(".", 1)[-1] == "partial" \
                                and dec.args and _is_jit_expr(dec.args[0]):
                            roots.append((mod, node))
                        elif _root_kind(dec.func):
                            roots.append((mod, node))
            elif isinstance(node, ast.Call):
                kind = _root_kind(node.func)
                if kind is None or not node.args:
                    continue
                scope = _enclosing_scope(mod, node)
                roots.extend(_resolve_callable(index, mod, scope,
                                               node.args[0]))
                # partial(jax.jit, f) spelled as jax.jit(partial(f, ...))
                first = node.args[0]
                if isinstance(first, ast.Call):
                    fd = dotted_name(first.func)
                    if fd and fd.rsplit(".", 1)[-1] == "partial" \
                            and first.args:
                        roots.extend(_resolve_callable(
                            index, mod, scope, first.args[0]))
    return roots


def _static_params(fn: ast.AST) -> Set[str]:
    """Parameter names considered static (non-traced)."""
    static: Set[str] = set()
    a = fn.args
    params = list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
    defaults = list(a.defaults)
    # align defaults with the tail of positional params
    pos = list(a.posonlyargs) + list(a.args)
    pos_defaults = {p.arg: d for p, d in
                    zip(pos[len(pos) - len(defaults):], defaults)}
    kw_defaults = {p.arg: d for p, d in zip(a.kwonlyargs, a.kw_defaults)
                   if d is not None}
    for p in params:
        if p.arg in STATIC_PARAM_NAMES:
            static.add(p.arg)
            continue
        ann = p.annotation
        if ann is not None:
            try:
                s = ast.unparse(ann)
            except Exception:
                s = ""
            base = s.strip("'\"")
            if base in _STATIC_ANNOTATIONS or "Config" in base:
                static.add(p.arg)
                continue
        d = pos_defaults.get(p.arg, kw_defaults.get(p.arg))
        if isinstance(d, ast.Constant) and isinstance(d.value, (bool, str)):
            static.add(p.arg)
    return static


def _param_names(fn: ast.AST) -> Set[str]:
    a = fn.args
    names = {p.arg for p in
             list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)}
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    return names


def _exempt_names_in_test(test: ast.AST) -> Set[str]:
    """Names whose appearance in a branch test is static: `x is None`,
    `"key" in x` (pytree structure), `x.shape/...`, `len(x)`,
    `isinstance(x, T)`."""
    exempt: Set[str] = set()
    for node in ast.walk(test):
        if isinstance(node, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            for operand in [node.left] + list(node.comparators):
                if isinstance(operand, ast.Name):
                    exempt.add(operand.id)
        elif isinstance(node, ast.Compare) and any(
                isinstance(op, (ast.In, ast.NotIn)) for op in node.ops):
            # `"key" in pool`: dict membership on a pytree is a
            # structure check, resolved at trace time
            for operand in node.comparators:
                if isinstance(operand, ast.Name):
                    exempt.add(operand.id)
        elif isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d in ("len", "isinstance", "getattr", "hasattr",
                     "callable"):
                for arg in ast.walk(node):
                    if isinstance(arg, ast.Name):
                        exempt.add(arg.id)
        elif isinstance(node, ast.Attribute) and \
                node.attr in _STATIC_TEST_ATTRS:
            if isinstance(node.value, ast.Name):
                exempt.add(node.value.id)
    return exempt


def _fn_label(fn: ast.AST) -> str:
    return getattr(fn, "name", "<lambda>")


_ARRAY_CALL_ROOTS = frozenset(("jnp", "jax", "lax"))


def _array_evidence(fn: ast.AST) -> Set[str]:
    """Names used as arrays somewhere in the function body: subscripted
    (``x[i]``), or passed bare to a jnp/jax/lax call.  Static python
    scalars and config flags never show this usage, so RC004 only fires
    on names that demonstrably hold traced data — the alternative (flag
    every branch on a parameter) drowns real hazards in static-config
    branches, which are the dominant idiom in this codebase."""
    names: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Subscript) \
                and isinstance(node.value, ast.Name):
            names.add(node.value.id)
        elif isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d and d.split(".")[0] in _ARRAY_CALL_ROOTS:
                for a in list(node.args) + [k.value for k in
                                            node.keywords]:
                    if isinstance(a, ast.Name):
                        names.add(a.id)
    return names


def _check_function(mod: _Module, fn: ast.AST,
                    out: List[Violation]) -> None:
    traced = _param_names(fn) - _static_params(fn)
    arrayish = traced & _array_evidence(fn)
    label = _fn_label(fn)
    body = fn.body if isinstance(fn.body, list) else [fn.body]

    # skip nested defs: they are visited when (and only when) reachable
    nested = {id(n) for top in body for n in ast.walk(top)
              if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)) and n is not fn}

    def in_nested(node) -> bool:
        return id(node) in nested_members

    nested_members: Set[int] = set()
    for top in body:
        for n in ast.walk(top):
            if id(n) in nested:
                for sub in ast.walk(n):
                    if sub is not n:
                        nested_members.add(id(sub))

    for top in body:
        for node in ast.walk(top):
            if in_nested(node):
                continue
            if isinstance(node, ast.Call):
                d = dotted_name(node.func)
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item" and not node.args:
                    out.append(Violation(
                        CHECKER, "RC001", mod.path, node.lineno,
                        f"{label}/.item",
                        f".item() in jit-reachable '{label}': device "
                        f"sync stalls the dispatch pipeline and breaks "
                        f"async execution"))
                elif d in ("float", "int", "bool") and len(node.args) == 1 \
                        and isinstance(node.args[0], ast.Name) \
                        and node.args[0].id in traced:
                    out.append(Violation(
                        CHECKER, "RC002", mod.path, node.lineno,
                        f"{label}/{d}({node.args[0].id})",
                        f"{d}() on traced '{node.args[0].id}' in "
                        f"jit-reachable '{label}': host sync / "
                        f"ConcretizationTypeError"))
                elif d and "." in d and d.split(".")[0] in _NP_ROOTS \
                        and d.rsplit(".", 1)[-1] in _NP_HOST_CALLS:
                    names = {n.id for a in node.args
                             for n in ast.walk(a)
                             if isinstance(n, ast.Name)}
                    hit = sorted(names & traced)
                    if hit:
                        out.append(Violation(
                            CHECKER, "RC003", mod.path, node.lineno,
                            f"{label}/{d}({hit[0]})",
                            f"{d}() on traced '{hit[0]}' in "
                            f"jit-reachable '{label}': device→host "
                            f"transfer at trace time"))
            elif isinstance(node, (ast.If, ast.While)):
                exempt = _exempt_names_in_test(node.test)
                hits = sorted({n.id for n in ast.walk(node.test)
                               if isinstance(n, ast.Name)
                               and isinstance(n.ctx, ast.Load)
                               and n.id in arrayish} - exempt)
                if hits:
                    kw = "while" if isinstance(node, ast.While) else "if"
                    out.append(Violation(
                        CHECKER, "RC004", mod.path, node.lineno,
                        f"{label}/{kw}({hits[0]})",
                        f"python {kw} on traced '{hits[0]}' in "
                        f"jit-reachable '{label}': retrace per value "
                        f"(use lax.cond/jnp.where)"))
            elif isinstance(node, ast.Attribute) \
                    and isinstance(node.ctx, ast.Load):
                inner = node.value
                if isinstance(inner, ast.Attribute) \
                        and isinstance(inner.value, ast.Name) \
                        and inner.value.id == "self" \
                        and inner.attr in ("config", "cfg", "args"):
                    out.append(Violation(
                        CHECKER, "RC005", mod.path, node.lineno,
                        f"{label}/self.{inner.attr}.{node.attr}",
                        f"'self.{inner.attr}.{node.attr}' read inside "
                        f"jit-reachable '{label}': mutable config must "
                        f"be resolved once at __init__ into a frozen "
                        f"attribute, not at trace time"))


def check(repo: Repo, baseline=None) -> List[Violation]:
    index = _Index(repo, "megatron_llm_tpu")
    roots = _find_roots(index)
    out: List[Violation] = []
    seen: Set[int] = set()
    queue: List[Tuple[_Module, ast.AST]] = list(roots)
    while queue:
        mod, fn = queue.pop()
        if fn is None or id(fn) in seen:
            continue
        seen.add(id(fn))
        _check_function(mod, fn, out)
        # follow calls (incl. callables handed to lax combinators)
        scope_base = mod.scopes.get(id(fn), _Scope(None, ()))
        scope = _Scope(scope_base.cls, scope_base.chain + (fn,))
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for top in body:
            for node in ast.walk(top):
                if not isinstance(node, ast.Call):
                    continue
                queue.extend(_resolve_callable(index, mod, scope,
                                               node.func))
                d = dotted_name(node.func)
                if d and d.rsplit(".", 1)[-1] in _TRACING_COMBINATORS:
                    for arg in list(node.args) + [
                            kw.value for kw in node.keywords]:
                        if isinstance(arg, (ast.Name, ast.Attribute,
                                            ast.Lambda)):
                            queue.extend(_resolve_callable(
                                index, mod, scope, arg))
    return out
