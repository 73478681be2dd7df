"""Checker ``stdlib``: the stdlib-only contract for report/bench tools.

``serve_bench.py``, ``serve_report.py``, ``trace_report.py``,
``telemetry_report.py``, ``health_report.py`` and
``serve_router.py`` are documented to run anywhere — a laptop reading
a JSONL dump, a CI box without jax — so a ``jax`` (or ``numpy``, or
``requests``) import sneaking into one of them breaks the contract
silently for everyone who relied on it.  Gate:

* a ``tools/*.py`` file is gated when its module docstring claims
  ``stdlib-only`` or it is in :data:`GATED_TOOLS`;
* every module-scope import in a gated file must be stdlib
  (``sys.stdlib_module_names``), or an explicitly allowed first-party
  module (:data:`ALLOWED_FIRST_PARTY`), or inside a
  ``try/except ImportError`` guard (documented graceful degradation);
* ``SG002``: each allowed first-party module is itself re-checked one
  level deep — its own unguarded module-scope imports must be stdlib,
  so the allowance can't smuggle jax in transitively (the
  "keep this module jax-free" contract in ``serving/router.py``).
"""

from __future__ import annotations

import ast
import sys
from typing import Dict, List, Set

from megatron_llm_tpu.analysis.core import Repo, Violation

CHECKER = "stdlib"

#: gated regardless of docstring (the documented stdlib-only surface)
GATED_TOOLS = frozenset((
    "tools/serve_bench.py",
    "tools/serve_report.py",
    "tools/serve_router.py",
    "tools/telemetry_report.py",
    "tools/trace_report.py",
    "tools/health_report.py",
    "tools/graft_lint.py",
))

#: gated file -> first-party modules it may import.  Each allowance is
#: itself checked one level deep (SG002): the named module's unguarded
#: module-scope imports must be stdlib or first-party.
ALLOWED_FIRST_PARTY: Dict[str, Set[str]] = {
    "tools/graft_lint.py": {"megatron_llm_tpu.analysis",
                            "megatron_llm_tpu"},
}

_FIRST_PARTY_ROOTS = frozenset(("megatron_llm_tpu", "tools"))

# sys.stdlib_module_names is 3.10+; this linter targets the repo's
# pinned runtime so no fallback table is maintained
_STDLIB = frozenset(getattr(sys, "stdlib_module_names", ()))


def _is_gated(repo: Repo, rel: str) -> bool:
    if rel in GATED_TOOLS:
        return True
    tree = repo.tree(rel)
    if tree is None:
        return False
    doc = ast.get_docstring(tree) or ""
    return "stdlib-only" in doc or "stdlib only" in doc


def _guarded_import_lines(tree: ast.AST) -> Set[int]:
    """Lines of imports inside try/except ImportError (or TYPE_CHECKING
    blocks) — allowed as documented graceful degradation."""
    guarded: Set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Try):
            catches_import = any(
                h.type is None or any(
                    n in ast.dump(h.type)
                    for n in ("ImportError", "ModuleNotFoundError",
                              "Exception"))
                for h in node.handlers)
            if catches_import:
                for sub in node.body:
                    for n in ast.walk(sub):
                        if isinstance(n, (ast.Import, ast.ImportFrom)):
                            guarded.add(n.lineno)
        elif isinstance(node, ast.If):
            t = ast.dump(node.test)
            if "TYPE_CHECKING" in t:
                for sub in node.body:
                    for n in ast.walk(sub):
                        if isinstance(n, (ast.Import, ast.ImportFrom)):
                            guarded.add(n.lineno)
    return guarded


def _module_scope_imports(tree: ast.AST):
    """(modname, lineno) for every import statement NOT inside a
    function/class body (module scope, including inside module-level
    try/if — those are filtered separately by _guarded_import_lines)."""
    out = []

    def visit(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            if isinstance(child, ast.Import):
                for a in child.names:
                    out.append((a.name, child.lineno))
            elif isinstance(child, ast.ImportFrom):
                if child.level == 0 and child.module:
                    out.append((child.module, child.lineno))
            else:
                visit(child)

    visit(tree)
    return out


def _violations_for(repo: Repo, rel: str, code: str,
                    allowed_first_party: Set[str]) -> List[Violation]:
    tree = repo.tree(rel)
    if tree is None:
        return []
    guarded = _guarded_import_lines(tree)
    out: List[Violation] = []
    for modname, line in _module_scope_imports(tree):
        if line in guarded:
            continue
        root = modname.split(".")[0]
        if root in _STDLIB or root == "__future__":
            continue
        if any(modname == a or modname.startswith(a + ".")
               for a in allowed_first_party):
            continue
        if root in _FIRST_PARTY_ROOTS:
            out.append(Violation(
                CHECKER, code, rel, line, modname,
                f"unguarded first-party import '{modname}' in "
                f"stdlib-only file — add to ALLOWED_FIRST_PARTY (with "
                f"its own SG002 transitive check) or guard with "
                f"try/ImportError"))
        else:
            out.append(Violation(
                CHECKER, code, rel, line, modname,
                f"non-stdlib import '{modname}' in stdlib-only tool — "
                f"this file is documented to run without {root} "
                f"installed"))
    return out


def check(repo: Repo, baseline=None) -> List[Violation]:
    out: List[Violation] = []
    checked_first_party: Set[str] = set()
    for rel in repo.py_files("tools"):
        if not _is_gated(repo, rel):
            continue
        allowed = ALLOWED_FIRST_PARTY.get(rel, set())
        out.extend(_violations_for(repo, rel, "SG001", allowed))
        checked_first_party |= allowed
    # SG002: one-level transitive check of every allowance — an allowed
    # first-party module may import package siblings (SG002 cares about
    # third-party leaks, not package structure), but not e.g. jax
    for modname in sorted(checked_first_party):
        rel = modname.replace(".", "/") + ".py"
        if not repo.exists(rel):
            rel = modname.replace(".", "/") + "/__init__.py"
        if repo.exists(rel):
            siblings = {m for m, _l in _module_scope_imports(
                repo.tree(rel) or ast.parse(""))
                if m.split(".")[0] in _FIRST_PARTY_ROOTS}
            out.extend(_violations_for(repo, rel, "SG002",
                                       checked_first_party | siblings))
    return out
