"""Tracer-hazard linter: AST checks for the jit-tracing bug classes the
OoO JIT codebase has actually hit.

Three rules, each named after the failure it prevents:

``TH001`` — jitted closure captures an array-derived value as a constant.
    XLA codegens arrays EMBEDDED in a jitted function (closure captures)
    differently than arrays passed as traced arguments — last-ulp FMA and
    fusion differences — and silently pins the captured buffer alive.
    The stacked-template scan bodies were bitten by exactly this: every
    per-layer param must enter the scan as an ``xs`` argument, never a
    closure. The rule flags any jit-rooted function, nested inside
    another function, whose free variables resolve to an enclosing
    function's *array-derived* bindings (seeded by parameters named
    ``params``/``*_p`` and propagated through subscripts, attributes,
    calls and tree maps). Module-level bindings are exempt — they are
    deliberate (memoized weights, static tables).

``TH002`` — plan-cache key function omits a field ``bind()`` cannot fix.
    ``ProgramTemplate.bind`` rebinds only per-step env state; everything
    else a template closes over must be captured by its plan-cache key
    or a stale template silently serves the wrong closures. Key
    functions (``*_cache_key``) must reference the known-irreplaceable
    ingredients: object identity (``id(``), dtype (``.dtype``) and cache
    geometry (``.shape``).

``TH003`` — raw glue math called outside a jitted context.
    Eager execution of the attention/MoE/SSM glue helpers computes
    different last-ulp bits than the same helper inside a jitted program,
    and dispatches each of its ops on its own. Direct calls to the raw
    helpers are only legal inside a jit-rooted function chain (the closure
    some ``jax.jit`` call roots, including jit factories, such as the
    stacked templates' scan bodies) or in the helper's defining module
    (the analytic baseline path).

Jit-rootedness is derived per module: ``@jax.jit`` /
``functools.partial(jax.jit, ...)`` decorations, ``jax.jit(name)`` /
``jax.jit(lambda ...)`` call sites, and the factory pattern — a function
``g`` with ``jax.jit(g(...))`` somewhere roots every function ``g``
returns. Resolution is per-module and name-based, deliberately
conservative in both directions for a lint (not a verifier).

Run as::

    python -m repro.analysis.lint [paths...] [--strict] [--json]

with no paths it lints the whole ``repro`` package. ``--strict`` exits
nonzero on any finding (the CI gate); ``--json`` emits machine-readable
findings.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# the eager/jitted bit-identity frontier: raw math helpers whose results
# differ in the last ulp between eager and traced execution (TH003)
RAW_GLUE_HELPERS = frozenset({
    "_gqa_decode_attend", "_causal_prefill_attend",   # core/jit.py
    "decode_core",                                    # models/ssm.py
    "route", "dispatch_tokens", "combine_tokens",     # models/moe.py
})

# what a template plan-cache key function must visibly capture (TH002)
CACHE_KEY_INGREDIENTS = (
    ("id(", "object identity (id(...))"),
    (".dtype", "dtype"),
    (".shape", "cache geometry (.shape)"),
)

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


@dataclasses.dataclass
class Finding:
    code: str                      # "TH001" | "TH002" | "TH003"
    path: str
    line: int
    symbol: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.code} [{self.symbol}] " \
               f"{self.message}"


# ---------------------------------------------------------------------------
# AST utilities
# ---------------------------------------------------------------------------

def _is_jax_jit(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name) and node.value.id == "jax")


def _is_partial_jax_jit(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call) or not node.args:
        return False
    f = node.func
    name_ok = (isinstance(f, ast.Name) and f.id == "partial") or \
        (isinstance(f, ast.Attribute) and f.attr == "partial")
    return name_ok and _is_jax_jit(node.args[0])


def _arg_names(node: ast.AST) -> List[str]:
    a = node.args
    args = list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
    if a.vararg:
        args.append(a.vararg)
    if a.kwarg:
        args.append(a.kwarg)
    return [x.arg for x in args]


def _target_names(target: ast.AST) -> List[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in target.elts:
            out.extend(_target_names(e))
        return out
    if isinstance(target, ast.Starred):
        return _target_names(target.value)
    return []


def _bound_names(fn: ast.AST) -> Set[str]:
    """Every name bound anywhere inside ``fn``'s subtree (params,
    assignments, loop/with/except targets, defs, imports) — the
    complement of the free-variable set."""
    bound: Set[str] = set(_arg_names(fn))
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            bound.add(node.name)
            bound.update(_arg_names(node))
        elif isinstance(node, ast.Lambda):
            bound.update(_arg_names(node))
        elif isinstance(node, ast.ClassDef):
            bound.add(node.name)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                bound.update(_target_names(t))
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            bound.update(_target_names(node.target))
        elif isinstance(node, ast.NamedExpr):
            bound.update(_target_names(node.target))
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            bound.update(_target_names(node.target))
        elif isinstance(node, ast.comprehension):
            bound.update(_target_names(node.target))
        elif isinstance(node, ast.withitem) and node.optional_vars:
            bound.update(_target_names(node.optional_vars))
        elif isinstance(node, ast.ExceptHandler) and node.name:
            bound.add(node.name)
        elif isinstance(node, ast.Import):
            for al in node.names:
                bound.add(al.asname or al.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            for al in node.names:
                bound.add(al.asname or al.name)
    return bound


def _free_names(fn: ast.AST) -> Set[str]:
    loads = {n.id for n in ast.walk(fn)
             if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}
    return loads - _bound_names(fn)


def _own_statements(fn: ast.AST) -> Iterable[ast.stmt]:
    """Statements in ``fn``'s own scope: recurse through control flow but
    never into nested function/class definitions."""
    def walk(body: Sequence[ast.stmt]) -> Iterable[ast.stmt]:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            yield stmt
            for field in ("body", "orelse", "finalbody"):
                yield from walk(getattr(stmt, field, ()) or ())
            for h in getattr(stmt, "handlers", ()) or ():
                yield from walk(h.body)
    yield from walk(getattr(fn, "body", ()) if not
                    isinstance(fn, ast.Lambda) else ())


def _derived_names(fn: ast.AST) -> Set[str]:
    """Array-derived bindings of one function scope: parameters named
    ``params``/``*_p`` seed the set; assignments whose value references a
    derived name (subscripts, attributes, calls — tree_map included —
    and containers) propagate it forward. Two passes close the common
    chains without a full fixpoint."""
    derived = {a for a in _arg_names(fn)
               if a == "params" or a.endswith("_p")}
    if isinstance(fn, ast.Lambda):
        return derived
    for _ in range(2):
        for stmt in _own_statements(fn):
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
                targets, value = [stmt.target], stmt.value
            else:
                continue
            if value is None:
                continue
            refs = {n.id for n in ast.walk(value)
                    if isinstance(n, ast.Name)
                    and isinstance(n.ctx, ast.Load)}
            if refs & derived:
                for t in targets:
                    derived.update(_target_names(t))
    return derived


# ---------------------------------------------------------------------------
# per-module analysis
# ---------------------------------------------------------------------------

class _Module:
    def __init__(self, path: Path, tree: ast.Module):
        self.path = path
        self.tree = tree
        self.parents: Dict[ast.AST, ast.AST] = {
            child: parent for parent in ast.walk(tree)
            for child in ast.iter_child_nodes(parent)}
        self.functions = [n for n in ast.walk(tree)
                          if isinstance(n, _FUNC_NODES)]
        self.top_level_defs = self._top_level_defs()
        self.rooted = self._jit_rooted()

    def _top_level_defs(self) -> Set[str]:
        names: Set[str] = set()
        for stmt in self.tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                names.add(stmt.name)
            elif isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    names.update(_target_names(t))
        return names

    def enclosing_chain(self, node: ast.AST) -> List[ast.AST]:
        """Enclosing function nodes, innermost first (node excluded)."""
        chain: List[ast.AST] = []
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, _FUNC_NODES):
                chain.append(cur)
            cur = self.parents.get(cur)
        return chain

    def _jit_rooted(self) -> Set[ast.AST]:
        """Function nodes some ``jax.jit`` call (transitively) roots."""
        rooted: Set[ast.AST] = set()
        rooted_names: Set[str] = set()
        factory_names: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and _is_jax_jit(node.func) \
                    and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Name):
                    rooted_names.add(arg.id)
                elif isinstance(arg, ast.Lambda):
                    rooted.add(arg)
                elif isinstance(arg, ast.Call) \
                        and isinstance(arg.func, ast.Name):
                    factory_names.add(arg.func.id)
        for fn in self.functions:
            if isinstance(fn, ast.Lambda):
                continue
            if fn.name in rooted_names:
                rooted.add(fn)
            for dec in fn.decorator_list:
                if _is_jax_jit(dec) or _is_partial_jax_jit(dec):
                    rooted.add(fn)
        # factory pattern: jax.jit(g(...)) roots whatever g returns
        for fn in self.functions:
            if isinstance(fn, ast.Lambda) or fn.name not in factory_names:
                continue
            returned: Set[str] = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Return) and node.value is not None:
                    if isinstance(node.value, ast.Name):
                        returned.add(node.value.id)
                    elif isinstance(node.value, ast.Lambda):
                        rooted.add(node.value)
            for nested in ast.walk(fn):
                if isinstance(nested, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)) \
                        and nested.name in returned:
                    rooted.add(nested)
        return rooted


def _fn_name(fn: ast.AST) -> str:
    return getattr(fn, "name", "<lambda>")


def _check_th001(mod: _Module) -> List[Finding]:
    findings: List[Finding] = []
    derived_memo: Dict[ast.AST, Set[str]] = {}
    for fn in mod.rooted:
        chain = mod.enclosing_chain(fn)
        if not chain:
            continue               # module-level jit roots are deliberate
        for name in sorted(_free_names(fn)):
            for scope in chain:
                bound = _arg_names(scope) if isinstance(scope, ast.Lambda) \
                    else sorted(_bound_names(scope))
                if name not in bound:
                    continue
                if scope not in derived_memo:
                    derived_memo[scope] = _derived_names(scope)
                if name in derived_memo[scope]:
                    findings.append(Finding(
                        "TH001", str(mod.path), fn.lineno, _fn_name(fn),
                        f"jit-rooted function closes over array-derived "
                        f"'{name}' from enclosing '{_fn_name(scope)}' — "
                        f"XLA bakes it in as a constant (last-ulp drift, "
                        f"pinned buffer); pass it as a traced argument"))
                break              # name resolved at the nearest binder
    return findings


def _check_th002(mod: _Module) -> List[Finding]:
    findings: List[Finding] = []
    for fn in mod.functions:
        if isinstance(fn, ast.Lambda) or not fn.name.endswith("_cache_key"):
            continue
        src = ast.unparse(fn)
        missing = [label for needle, label in CACHE_KEY_INGREDIENTS
                   if needle not in src]
        if missing:
            findings.append(Finding(
                "TH002", str(mod.path), fn.lineno, fn.name,
                f"plan-cache key function omits field(s) bind() does not "
                f"rebind: {', '.join(missing)} — a stale template would "
                f"silently serve the wrong closures"))
    return findings


def _check_th003(mod: _Module) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        callee = f.id if isinstance(f, ast.Name) else \
            f.attr if isinstance(f, ast.Attribute) else None
        if callee not in RAW_GLUE_HELPERS:
            continue
        if callee in mod.top_level_defs:
            continue               # the defining module's analytic path
        chain = mod.enclosing_chain(node)
        if any(fn in mod.rooted for fn in chain):
            continue               # inside a jit-rooted closure chain
        where = _fn_name(chain[0]) if chain else "<module>"
        findings.append(Finding(
            "TH003", str(mod.path), node.lineno, where,
            f"raw glue helper '{callee}' called eagerly (outside any "
            f"jit-rooted chain) — call it inside a jitted body (a "
            f"stacked template's scan) so it runs compiled"))
    return findings


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def lint_file(path: Path) -> List[Finding]:
    try:
        tree = ast.parse(path.read_text(), filename=str(path))
    except SyntaxError as e:
        return [Finding("TH000", str(path), e.lineno or 0, "<parse>",
                        f"syntax error: {e.msg}")]
    mod = _Module(path, tree)
    findings = _check_th001(mod) + _check_th002(mod) + _check_th003(mod)
    return sorted(findings, key=lambda f: (f.path, f.line, f.code))


def lint_paths(paths: Sequence[Path]) -> List[Finding]:
    files: List[Path] = []
    for p in paths:
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        else:
            files.append(p)
    findings: List[Finding] = []
    for f in files:
        findings.extend(lint_file(f))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="Tracer-hazard linter (TH001 jit-closure capture, "
                    "TH002 cache-key completeness, TH003 eager raw-glue "
                    "calls).")
    ap.add_argument("paths", nargs="*",
                    help="files/directories to lint (default: the repro "
                         "package)")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero if any finding is reported")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit machine-readable JSON findings")
    args = ap.parse_args(argv)
    paths = [Path(p) for p in args.paths] \
        or [Path(__file__).resolve().parents[1]]
    findings = lint_paths(paths)
    if args.as_json:
        print(json.dumps([dataclasses.asdict(f) for f in findings],
                         indent=2))
    else:
        for f in findings:
            print(f.render())
        print(f"{len(findings)} finding(s)")
    return 1 if (findings and args.strict) else 0


if __name__ == "__main__":
    sys.exit(main())
