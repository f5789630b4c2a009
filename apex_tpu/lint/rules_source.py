"""Engine 1: source-AST rules over ``apex_tpu/`` + ``examples/`` + ``benchmarks/``.

Each rule mechanizes one project invariant that previously lived only in
CLAUDE.md prose or an ad-hoc test walker (the ``comm:``-scope check promoted
from tests/test_diagnose.py). Rules are named and individually suppressable
(``# lint: disable=<rule> -- why``, findings.py); ``python -m apex_tpu.lint
--strict`` exits non-zero on any unsuppressed violation.

No reference analog (package docstring, ``apex_tpu/lint/__init__.py``): the
rule set encodes THIS repo's invariants --

- ``comm-scope``            every collective verb runs under a ``comm:``
                            named scope (parallel/collectives.py:20-24)
- ``grad-collective``       no differentiated loss returns a bare
                            ``lax.psum``/``pmean`` (its transpose over-counts
                            by the axis size under ``check_vma=False``; use
                            the identity-backward wrapper,
                            tensor_parallel/mappings.py:62-79)
- ``pallas-interpret``      every ``pallas_call`` site carries an
                            ``interpret=`` path so the suite runs off-TPU
- ``module-citation``       every apex_tpu module docstring cites its
                            reference file (or states it has no reference)
- ``exception-retention``   no ``except`` handler stores the caught
                            exception object past its block (tracebacks pin
                            device buffers -- the bench.py OOM-ladder trap,
                            monitor/hbm.py:84-99)
"""

from __future__ import annotations

import ast
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from apex_tpu.lint.findings import Finding, LintReport, Suppressions

# ---------------------------------------------------------------------------
# shared-constant extraction (the collectives.py introspection hook)
# ---------------------------------------------------------------------------

# fallbacks if the static extraction below ever fails; the canonical copies
# live next to the verbs they describe (parallel/collectives.py)
_DEFAULT_COMM_PRIMS = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "psum_scatter",
    "ppermute", "all_to_all", "pshuffle", "all_gather_invariant",
}
_DEFAULT_SCOPE_HELPERS = ("_comm", "collective_scope")

_COMM_CONST_CACHE: Optional[Tuple[set, tuple]] = None


def repo_root() -> str:
    """The tree this package lints: the repo containing ``apex_tpu/``."""
    here = os.path.dirname(os.path.abspath(__file__))  # .../apex_tpu/lint
    return os.path.dirname(os.path.dirname(here))


def _shared_comm_constants() -> Tuple[set, tuple]:
    """``(COMM_SCOPE_PRIMS, COMM_SCOPE_HELPERS)`` read STATICALLY from
    parallel/collectives.py (ast.literal_eval -- no jax import), so the
    linter and the verbs it polices share one source of truth."""
    global _COMM_CONST_CACHE
    if _COMM_CONST_CACHE is not None:
        return _COMM_CONST_CACHE
    prims, helpers = set(_DEFAULT_COMM_PRIMS), _DEFAULT_SCOPE_HELPERS
    path = os.path.join(repo_root(), "apex_tpu", "parallel", "collectives.py")
    try:
        tree = ast.parse(open(path, encoding="utf-8").read(), filename=path)
        for node in tree.body:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            name = getattr(node.targets[0], "id", None)
            if name == "COMM_SCOPE_PRIMS":
                prims = set(ast.literal_eval(node.value))
            elif name == "COMM_SCOPE_HELPERS":
                helpers = tuple(ast.literal_eval(node.value))
    except Exception:  # noqa: BLE001 - fall back to the builtin copies
        pass
    _COMM_CONST_CACHE = (prims, helpers)
    return _COMM_CONST_CACHE


# ---------------------------------------------------------------------------
# rule registry + module context
# ---------------------------------------------------------------------------

RULES: Dict[str, Tuple[Callable, str]] = {}


def rule(name: str, description: str):
    def deco(fn):
        RULES[name] = (fn, description)
        return fn
    return deco


class ModuleCtx:
    """One parsed file handed to every rule."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.tree = ast.parse(source, filename=path)


def _own_body_walk(node: ast.AST) -> Iterable[ast.AST]:
    """Walk ``node``'s subtree WITHOUT descending into nested function/class
    definitions -- 'this scope's own statements'."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(n))


def _iter_scopes(tree: ast.Module):
    """Yield ``(scope_node, name)`` for the module and every function."""
    yield tree, "<module>"
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node, node.name


def _call_name(func: ast.AST) -> Optional[str]:
    """Trailing name of a call target: ``a.b.c(...)`` -> ``c``."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


# ---------------------------------------------------------------------------
# comm-scope (promoted from tests/test_diagnose.py's ad-hoc walker)
# ---------------------------------------------------------------------------

_COMM_CANONICAL = ("apex_tpu/parallel/collectives.py",
                   "apex_tpu/transformer/tensor_parallel/mappings.py")


def _is_comm_scope_target(ctx: ModuleCtx) -> bool:
    """The rule applies to the canonical verb modules, to any module that
    imports the scope helper, and to any module carrying the explicit
    ``LINT_COMM_SCOPE = True`` marker (the opt-in introspection hook)."""
    if any(ctx.relpath.endswith(p) for p in _COMM_CANONICAL):
        return True
    for node in ctx.tree.body:
        if (isinstance(node, ast.ImportFrom)
                and node.module == "apex_tpu.monitor.comms"
                and any(a.name == "collective_scope" for a in node.names)):
            return True
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and getattr(node.targets[0], "id", None) == "LINT_COMM_SCOPE"):
            return True
    return False


def _comm_scope_walk(tree: ast.Module) -> Tuple[List[Tuple[str, int, List[str]]], int]:
    """``(violations, verb_fn_count)``: top-level functions that CALL a lax
    collective without ALSO calling the ``comm:`` scope helper somewhere in
    their body -- the accounting contract every verb must carry."""
    prims, helpers = _shared_comm_constants()

    def is_lax_collective(func):
        if not isinstance(func, ast.Attribute) or func.attr not in prims:
            return False
        val = func.value
        return (isinstance(val, ast.Name) and val.id == "lax") or (
            isinstance(val, ast.Attribute) and val.attr == "lax")

    def calls_in(node, pred):
        return [n for n in ast.walk(node)
                if isinstance(n, ast.Call) and pred(n.func)]

    violations, verbs = [], 0
    for node in tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        collectives = calls_in(node, is_lax_collective)
        if not collectives:
            continue
        verbs += 1
        if not calls_in(node, lambda f: _call_name(f) in helpers):
            names = sorted({c.func.attr for c in collectives})
            violations.append((node.name, node.lineno, names))
    return violations, verbs


def comm_scope_check(path: str) -> Tuple[List[Tuple[str, List[str]]], int]:
    """Public hook for tests (the thin invocation test_diagnose.py now
    makes): ``(violations, verb_fn_count)`` for one file, in the shape the
    original ad-hoc walker returned."""
    tree = ast.parse(open(path, encoding="utf-8").read(), filename=path)
    violations, verbs = _comm_scope_walk(tree)
    return [(name, prims) for name, _, prims in violations], verbs


@rule("comm-scope",
      "collective verbs must run under a comm:<verb> named scope "
      "(monitor/comms.py) so per-axis accounting stays complete")
def _rule_comm_scope(ctx: ModuleCtx):
    if not _is_comm_scope_target(ctx):
        return
    violations, _ = _comm_scope_walk(ctx.tree)
    for name, lineno, prims in violations:
        yield lineno, (
            f"function '{name}' calls lax collective(s) {prims} without a "
            f"comm: scope (_comm/collective_scope) -- per-axis comm "
            f"accounting silently drops this verb")


# ---------------------------------------------------------------------------
# grad-collective
# ---------------------------------------------------------------------------

_GRAD_FNS = {"grad", "value_and_grad"}
_LOSS_COLLECTIVES = {"psum", "pmean"}


def _grad_targets(tree: ast.Module):
    """``(call_node, target)`` pairs: the function object each
    ``jax.grad``/``value_and_grad`` call differentiates, resolved when it is
    a same-file def or an inline lambda."""
    defs: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _call_name(node.func) in _GRAD_FNS):
            continue
        if not node.args:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Lambda):
            yield node, arg
        elif isinstance(arg, ast.Name):
            for target in defs.get(arg.id, []):
                yield node, target


def _loss_collective_calls(expr: ast.AST) -> List[ast.Call]:
    return [n for n in ast.walk(expr)
            if isinstance(n, ast.Call)
            and _call_name(n.func) in _LOSS_COLLECTIVES]


@rule("grad-collective",
      "a differentiated loss must not return a bare lax.psum/pmean -- the "
      "transpose over-counts by the axis size under check_vma=False; use "
      "the identity-backward wrapper (tensor_parallel/mappings.py)")
def _rule_grad_collective(ctx: ModuleCtx):
    seen = set()
    for _call, target in _grad_targets(ctx.tree):
        if id(target) in seen:
            continue
        seen.add(id(target))
        if isinstance(target, ast.Lambda):
            returned = [target.body]
            assigns: Dict[str, ast.AST] = {}
            fname = "<lambda>"
        else:
            returned = [n.value for n in _own_body_walk(target)
                        if isinstance(n, ast.Return) and n.value is not None]
            assigns = {}
            for n in _own_body_walk(target):
                if (isinstance(n, ast.Assign) and len(n.targets) == 1
                        and isinstance(n.targets[0], ast.Name)):
                    assigns[n.targets[0].id] = n.value
            fname = target.name
        # expand returned names one assignment deep (loss = pmean(...);
        # return loss), then scan the return expressions for collectives
        exprs = []
        for expr in returned:
            exprs.append(expr)
            for name_node in ast.walk(expr):
                if isinstance(name_node, ast.Name) and name_node.id in assigns:
                    exprs.append(assigns[name_node.id])
        for expr in exprs:
            for call in _loss_collective_calls(expr):
                verb = _call_name(call.func)
                yield call.lineno, (
                    f"'{fname}' is differentiated (jax.grad/value_and_grad) "
                    f"and returns a bare {verb} of its loss -- the transpose "
                    f"over-counts by the axis size; reduce AFTER the grad "
                    f"call or use the identity-backward psum "
                    f"(reduce_from_tensor_model_parallel_region)")


# ---------------------------------------------------------------------------
# pallas-interpret
# ---------------------------------------------------------------------------


@rule("pallas-interpret",
      "every pallas_call site must carry an interpret= path so the kernel "
      "runs on the off-TPU CPU suite (CLAUDE.md conventions)")
def _rule_pallas_interpret(ctx: ModuleCtx):
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node.func) == "pallas_call"):
            continue
        kws = {k.arg for k in node.keywords}
        if "interpret" not in kws and None not in kws:  # None = **kwargs
            yield node.lineno, (
                "pallas_call without an interpret= kwarg -- the kernel has "
                "no reachable interpret-mode path for the CPU test suite")


# ---------------------------------------------------------------------------
# module-citation
# ---------------------------------------------------------------------------

_CITE_FILE = re.compile(r"[\w.-]+\.(py|pyx|cu|cuh|cpp|cc|h|hpp)\b")
_CITE_DIR = re.compile(r"reference.{0,120}?[\w.-]+/", re.I | re.S)
_CITE_WAIVER = re.compile(
    r"no reference|reference\b[^.]{0,60}\bhas no|absent in the reference|"
    r"beyond the reference|not in the reference|new capability", re.I)


@rule("module-citation",
      "every apex_tpu module docstring cites the reference file whose "
      "semantics it preserves, or states it has no reference analog")
def _rule_module_citation(ctx: ModuleCtx):
    if not ctx.relpath.startswith("apex_tpu/"):
        return  # the convention covers the framework tree, not examples
    doc = ast.get_docstring(ctx.tree)
    if not doc:
        yield 1, "module has no docstring (convention: cite the reference " \
                 "file:line whose semantics it preserves)"
        return
    if not (_CITE_FILE.search(doc) or _CITE_DIR.search(doc)
            or _CITE_WAIVER.search(doc)):
        yield 1, ("module docstring cites no reference file/dir and does "
                  "not state the module has no reference analog")


# ---------------------------------------------------------------------------
# exception-retention
# ---------------------------------------------------------------------------


def _bare_name_in_display(value: ast.AST, name: str) -> bool:
    """True when ``value`` IS ``name`` or a tuple/list/set/dict display
    holding it as a direct element (``str(e)``/f-strings do not retain)."""
    if isinstance(value, ast.Name) and value.id == name:
        return True
    if isinstance(value, (ast.Tuple, ast.List, ast.Set)):
        return any(_bare_name_in_display(el, name) for el in value.elts)
    if isinstance(value, ast.Dict):
        return any(v is not None and _bare_name_in_display(v, name)
                   for v in list(value.keys) + list(value.values))
    return False


_RETAIN_METHODS = {"append", "add", "put", "insert", "appendleft", "extend"}


@rule("exception-retention",
      "an except handler must not store the caught exception object past "
      "its block -- the traceback pins device buffers (the OOM-ladder "
      "leak, monitor/hbm.py; CLAUDE.md gotchas); keep str(e) instead")
def _rule_exception_retention(ctx: ModuleCtx):
    for scope, _name in _iter_scopes(ctx.tree):
        own = list(_own_body_walk(scope))
        handlers = [n for n in own
                    if isinstance(n, ast.ExceptHandler) and n.name]
        for h in handlers:
            e = h.name
            inside = set()
            for body_node in h.body:
                inside.update(ast.walk(body_node))
            # names loaded in this scope OUTSIDE the handler: a plain-name
            # assignment of ``e`` that is later read escapes the block
            outside_loads = {n.id for n in own
                             if isinstance(n, ast.Name)
                             and isinstance(n.ctx, ast.Load)
                             and n not in inside}
            for n in inside:
                msg = None
                if isinstance(n, (ast.Return, ast.Yield)) and n.value is not None \
                        and _bare_name_in_display(n.value, e):
                    msg = f"handler returns the caught exception '{e}'"
                elif isinstance(n, ast.Assign) and _bare_name_in_display(n.value, e):
                    for t in n.targets:
                        if isinstance(t, (ast.Attribute, ast.Subscript)):
                            msg = (f"caught exception '{e}' stored into an "
                                   f"attribute/container")
                        elif isinstance(t, ast.Name) and t.id in outside_loads:
                            msg = (f"caught exception '{e}' assigned to "
                                   f"'{t.id}', which is read outside the "
                                   f"handler")
                elif (isinstance(n, ast.Call)
                      and isinstance(n.func, ast.Attribute)
                      and n.func.attr in _RETAIN_METHODS
                      and any(_bare_name_in_display(a, e) for a in n.args)):
                    msg = (f"caught exception '{e}' pushed into a container "
                           f"via .{n.func.attr}()")
                elif (isinstance(n, ast.Call)
                      and _call_name(n.func) == "setattr"
                      and any(_bare_name_in_display(a, e) for a in n.args)):
                    msg = f"caught exception '{e}' stored via setattr"
                if msg:
                    yield n.lineno, (
                        msg + " -- the exception's traceback pins every "
                        "device buffer in the failed frame (OOM forensics "
                        "must keep str(e), never e)")


# ---------------------------------------------------------------------------
# engine driver
# ---------------------------------------------------------------------------

DEFAULT_TREES = ("apex_tpu", "examples", "benchmarks")


def iter_files(paths: Optional[Iterable[str]] = None,
               root: Optional[str] = None) -> List[str]:
    root = root or repo_root()
    explicit = list(paths) if paths else None
    if explicit is not None:
        paths = explicit
    else:
        paths = [os.path.join(root, t) for t in DEFAULT_TREES]
        # plus the repo-root entry points (bench.py, __graft_entry__.py):
        # the OOM-retention and timing gotchas the rules cite live there
        paths.extend(os.path.join(root, f) for f in sorted(os.listdir(root))
                     if f.endswith(".py")
                     and os.path.isfile(os.path.join(root, f)))
    files = []
    for p in paths:
        if explicit is not None and not os.path.exists(p):
            # a typo'd CI path must fail loudly, never lint 0 files green
            raise ValueError(f"lint path does not exist: {p}")
        if os.path.isfile(p):
            files.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            files.extend(os.path.join(dirpath, f)
                         for f in sorted(filenames) if f.endswith(".py"))
    return files


def run_paths(paths: Optional[Iterable[str]] = None,
              rules: Optional[Iterable[str]] = None,
              root: Optional[str] = None) -> LintReport:
    """Run engine 1 over ``paths`` (default: the apex_tpu/examples/
    benchmarks trees). ``rules`` filters the registry by name."""
    root = root or repo_root()
    wanted = list(rules) if rules else list(RULES)
    unknown = set(wanted) - set(RULES)
    if unknown:
        raise ValueError(f"unknown lint rule(s): {sorted(unknown)}")
    selected = {name: RULES[name] for name in wanted}
    report = LintReport(rules_run=sorted(selected))
    for path in iter_files(paths, root=root):
        relpath = os.path.relpath(path, root)
        try:
            source = open(path, encoding="utf-8").read()
            ctx = ModuleCtx(path, relpath, source)
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            report.findings.append(Finding(
                rule="parse-error", path=relpath.replace(os.sep, "/"),
                line=getattr(e, "lineno", 1) or 1,
                message=f"cannot lint: {type(e).__name__}: {e}"))
            report.files_scanned += 1
            continue
        report.files_scanned += 1
        sup = None  # built on the first finding: findings-free files
        for name, (fn, _desc) in selected.items():  # never read the table
            for lineno, message in (fn(ctx) or ()):
                sup = Suppressions(source) if sup is None else sup
                hit = sup.match(name, lineno)
                report.findings.append(Finding(
                    rule=name, path=ctx.relpath, line=lineno, message=message,
                    suppressed=bool(hit), justification=hit[1] if hit else ""))
    report.findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return report
