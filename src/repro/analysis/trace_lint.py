"""AST trace-safety lint: host syncs, numpy-on-traced, Python branches.

Static companion of the jaxpr auditor: where the auditor inspects what a
registered entry point *traced to*, this lint inspects the *source* of
every module under ``src/repro`` — it catches violations in paths the
registry does not trace (new entry points, rarely-taken branches) and
reports them at the offending source line before anything runs.

Three rules:

``trace-host-sync``
    ``float(e)`` / ``int(e)`` / ``bool(e)`` / ``e.item()`` where ``e``
    contains a ``jnp.*`` / ``jax.lax.*`` / ``jax.scipy.*`` / ``jax.ops.*``
    /``jax.nn.*`` call (directly or through a local variable assigned from
    one).  Inside a jit trace this is a ``ConcretizationError`` waiting to
    happen; *outside* jit it is a silent blocking device round-trip — the
    class of bug the solver's setup path shipped (``float(jnp.linalg.
    norm(w))`` per hierarchy level).  Applied file-wide: build-time closure
    code is exactly where these hide.  The designated sync points
    (``jax.device_get`` / ``jax.block_until_ready`` and host values built
    from them) are not flagged — routing a scalarization through
    ``device_get`` is the documented way to *mark* it deliberate.

``trace-numpy-on-traced``
    ``np.*`` call inside a jit-traced scope whose arguments involve traced
    values: numpy forces a transfer and constant-folds under trace,
    silently baking one batch's values into the compiled executable.

``trace-python-branch``
    ``if`` (statement or expression) inside a jit-traced scope whose test
    involves a traced value or a ``jnp.*`` call.  Exemptions: ``is None``
    checks, ``isinstance``, and anything reached only through
    ``.shape`` / ``.ndim`` / ``.dtype`` / ``len()`` — shape math is static
    under trace and is how the kernels legitimately branch on padding.

Traced scopes are discovered statically, best-effort by construction:
functions decorated with ``jax.jit`` (including ``partial(jax.jit, ...)``,
honoring ``static_argnums``/``static_argnames``), functions passed by name
to ``jax.jit`` / ``shard_map`` / ``lax.while_loop``
/ ``lax.fori_loop`` / ``lax.scan`` / ``lax.cond``, plus module-local
functions those call (one call-graph closure, by simple name).  Nested
defs inside a traced scope are scanned with their *own* parameters treated
as untraced (the V-cycle's ``cycle(l, r)`` recursion takes static level
indices) — traced-ness flows through closure variables and ``jnp`` calls
instead.  Pallas kernel bodies are excluded: they receive ``Ref``s and
cannot host-sync.

Known limitation: the dataflow is flow-insensitive (facts are only ever
added), so REASSIGNING a device-derived name from a host boundary
(``x = jax.device_get(x)``) does not clear its derived status — bind the
host value to a NEW name instead (``host_x = jax.device_get(x)``), which
is also clearer to human readers about which side of the boundary a value
lives on.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.analysis.findings import (Finding, apply_pragmas, scan_pragmas)

# attribute roots whose calls produce traced/device values
_JAX_CALL_ROOTS = {"jnp"}
_JAX_CALL_PREFIXES = (("jax", "lax"), ("jax", "scipy"), ("jax", "ops"),
                      ("jax", "nn"), ("jax", "numpy"))
# designated sync points: calls through these are deliberate host landings
_SYNC_OK = {("jax", "device_get"), ("jax", "block_until_ready")}
# host boundaries: the *result* of these calls is a host value — syncs on
# values that already crossed through one are free, so dataflow pruning
# stops here (np.asarray(jnp_x) is the sync; int() of it afterwards isn't)
_HOST_BOUNDARY = {("np", "asarray"), ("np", "array"),
                  ("numpy", "asarray"), ("numpy", "array"),
                  ("jax", "device_get"), ("jax", "block_until_ready")}
_NP_ROOTS = {"np", "numpy"}
_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size"}

_TRACING_CALLEES = {
    # (dotted suffix) -> positions of function-valued args that get traced
    "jit": (0,),
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "scan": (0,),
    "cond": (1, 2),
    "shard_map": (0,),
}


def _attr_path(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """``a.b.c`` -> ("a", "b", "c"); None for anything not a pure path."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _is_jax_call(node: ast.Call) -> bool:
    path = _attr_path(node.func)
    if path is None:
        return False
    if path[:2] in _SYNC_OK:
        return False
    if path[0] in _JAX_CALL_ROOTS:
        return True
    return any(path[:len(p)] == p for p in _JAX_CALL_PREFIXES)


def _is_host_boundary(node: ast.Call) -> bool:
    path = _attr_path(node.func)
    return bool(path) and (path[:2] in _HOST_BOUNDARY
                           or path[-2:] in _HOST_BOUNDARY)


def _walk_pruned(node: ast.AST, prune_host: bool):
    """ast.walk, optionally skipping host-boundary call subtrees whole
    (their results live on the host regardless of what fed them)."""
    stack = [node]
    while stack:
        sub = stack.pop()
        if prune_host and isinstance(sub, ast.Call) \
                and _is_host_boundary(sub):
            continue
        yield sub
        stack.extend(ast.iter_child_nodes(sub))


def _contains_jax_call(node: ast.AST, prune_host: bool = False) -> bool:
    return any(isinstance(sub, ast.Call) and _is_jax_call(sub)
               for sub in _walk_pruned(node, prune_host))


class _NameUse(ast.NodeVisitor):
    """Names referenced in an expression, split into shape-shielded uses
    (only ever seen under ``.shape``/``.ndim``/``.dtype``/``len()``) and
    value uses."""

    def __init__(self, prune_host: bool = False):
        self.value_names: Set[str] = set()
        self.prune_host = prune_host

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr in _SHAPE_ATTRS:
            return  # anything under .shape is static metadata
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        if isinstance(node.func, ast.Name) and node.func.id == "len":
            return
        if isinstance(node.func, ast.Name) and node.func.id == "isinstance":
            return
        if self.prune_host and _is_host_boundary(node):
            return
        # the callee name itself is not a *value* use (msolve(r): msolve
        # being a traced-built closure does not make the test traced)
        for arg in node.args:
            self.visit(arg)
        for kw in node.keywords:
            self.visit(kw.value)

    def visit_Name(self, node: ast.Name):
        self.value_names.add(node.id)


def _value_names(node: ast.AST, prune_host: bool = False) -> Set[str]:
    v = _NameUse(prune_host)
    v.visit(node)
    return v.value_names


def _targets(t: ast.AST) -> Iterable[str]:
    if isinstance(t, ast.Name):
        yield t.id
    elif isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            yield from _targets(e)
    elif isinstance(t, ast.Starred):
        yield from _targets(t.value)


def _decorator_jit_info(fn: ast.AST) -> Optional[Tuple[Set[int], Set[str]]]:
    """(static_argnums, static_argnames) if the def is jit-decorated."""
    for dec in getattr(fn, "decorator_list", ()):
        target = dec
        static_nums: Set[int] = set()
        static_names: Set[str] = set()
        if isinstance(dec, ast.Call):
            path = _attr_path(dec.func)
            if path and path[-1] == "partial" and dec.args:
                target = dec.args[0]
                for kw in dec.keywords:
                    if kw.arg == "static_argnums":
                        static_nums = _const_int_set(kw.value)
                    elif kw.arg == "static_argnames":
                        static_names = _const_str_set(kw.value)
            else:
                target = dec.func  # jax.jit(static_argnames=...) form
                for kw in dec.keywords:
                    if kw.arg == "static_argnums":
                        static_nums = _const_int_set(kw.value)
                    elif kw.arg == "static_argnames":
                        static_names = _const_str_set(kw.value)
        path = _attr_path(target)
        if path and path[-1] == "jit":
            return static_nums, static_names
    return None


def _const_int_set(node: ast.AST) -> Set[int]:
    out: Set[int] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, int):
            out.add(sub.value)
    return out


def _const_str_set(node: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            out.add(sub.value)
    return out


def _fn_params(fn: ast.AST) -> List[str]:
    a = fn.args
    names = [p.arg for p in getattr(a, "posonlyargs", [])]
    names += [p.arg for p in a.args]
    if a.vararg:
        names.append(a.vararg.arg)
    names += [p.arg for p in a.kwonlyargs]
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


class _Scope:
    """One function to lint: its def node and which params are traced."""

    def __init__(self, node, traced_params: Set[str], why: str):
        self.node = node
        self.traced_params = traced_params
        self.why = why


def _collect_scopes(tree: ast.Module) -> List[_Scope]:
    """Discover traced scopes: jit-decorated defs, defs passed to tracing
    callees, and the module-local call closure over both."""
    defs: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)

    scopes: Dict[ast.AST, _Scope] = {}

    def add(node, traced: Set[str], why: str):
        if node not in scopes:
            scopes[node] = _Scope(node, traced, why)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            info = _decorator_jit_info(node)
            if info is not None:
                nums, names = info
                params = _fn_params(node)
                traced = {p for i, p in enumerate(params)
                          if i not in nums and p not in names}
                add(node, traced, "jit-decorated")
        if isinstance(node, ast.Call):
            path = _attr_path(node.func)
            if path is None:
                continue
            positions = _TRACING_CALLEES.get(path[-1])
            if positions is None:
                continue
            for pos in positions:
                if pos >= len(node.args):
                    continue
                arg = node.args[pos]
                if isinstance(arg, ast.Lambda):
                    add(arg, set(_fn_params(arg)), f"passed to {path[-1]}")
                elif isinstance(arg, ast.Name):
                    for d in defs.get(arg.id, []):
                        add(d, set(_fn_params(d)), f"passed to {path[-1]}")

    # one closure round: module-local functions called from traced scopes
    # are traced scopes themselves (their params conservatively untraced —
    # we cannot see the call's argument binding statically)
    frontier = list(scopes.values())
    while frontier:
        nxt: List[_Scope] = []
        for sc in frontier:
            for sub in ast.walk(sc.node):
                if isinstance(sub, ast.Call) and isinstance(sub.func,
                                                            ast.Name):
                    for d in defs.get(sub.func.id, []):
                        if d not in scopes:
                            scopes[d] = _Scope(d, set(),
                                               f"called from {sc.why}")
                            nxt.append(scopes[d])
        frontier = nxt
    return list(scopes.values())


def _traced_names_flow(fn, traced_params: Set[str]) -> Set[str]:
    """Forward-propagate traced-ness through simple assignments: a target
    is traced when its RHS uses a traced name by value (not through
    ``.shape``) or contains a ``jnp.*``-family call."""
    traced = set(traced_params)
    for _ in range(3):        # small fixpoint: assignment chains are short
        before = len(traced)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                rhs, tgts = node.value, node.targets
            elif isinstance(node, ast.AugAssign):
                rhs, tgts = node.value, [node.target]
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                rhs, tgts = node.value, [node.target]
            else:
                continue
            if (_value_names(rhs) & traced) or _contains_jax_call(rhs):
                for name in _targets_of(tgts):
                    traced.add(name)
        if len(traced) == before:
            break
    return traced


def _targets_of(tgts) -> Iterable[str]:
    for t in tgts:
        yield from _targets(t)


def _jnp_derived_names(fn) -> Set[str]:
    """Locals assigned (transitively) from ``jnp.*``-family calls — the
    host-sync rule's dataflow, applicable outside traced scopes too."""
    derived: Set[str] = set()
    for _ in range(3):
        before = len(derived)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                rhs, tgts = node.value, node.targets
            elif isinstance(node, ast.AugAssign):
                rhs, tgts = node.value, [node.target]
            else:
                continue
            if _contains_jax_call(rhs, prune_host=True) \
                    or (_value_names(rhs, prune_host=True) & derived):
                for name in _targets_of(tgts):
                    derived.add(name)
        if len(derived) == before:
            break
    return derived


def _own_nodes(fn) -> Iterable[ast.AST]:
    """Nodes of ``fn``'s body excluding nested function bodies (nested defs
    are linted as their own scopes with their own dataflow)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _exempt_test(test: ast.AST) -> bool:
    """``x is None`` / ``x is not None`` / isinstance checks are static."""
    if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
        return True
    if isinstance(test, ast.Call):
        path = _attr_path(test.func)
        if path and path[-1] == "isinstance":
            return True
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _exempt_test(test.operand)
    return False


def check_source(source: str, path: str) -> List[Finding]:
    """Lint one module's source; returns pragma-filtered findings."""
    allowed, findings = scan_pragmas(source, path)
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(file=path, line=e.lineno or 1, rule="trace-host-sync",
                        message=f"unparseable module: {e.msg}")]

    out: List[Finding] = list(findings)

    # ---- rule: trace-host-sync (file-wide) ------------------------------
    all_fns = [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in all_fns:
        derived = _jnp_derived_names(fn)

        def syncy(expr) -> bool:
            return (_contains_jax_call(expr, prune_host=True)
                    or bool(_value_names(expr, prune_host=True) & derived))

        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Name) and \
                    node.func.id in ("float", "int", "bool") and node.args:
                if syncy(node.args[0]):
                    out.append(Finding(
                        file=path, line=node.lineno, rule="trace-host-sync",
                        message=f"{node.func.id}() scalarizes a jax value "
                                f"in {fn.name}() — a blocking device "
                                f"round-trip; keep it on device or route "
                                f"through jax.device_get at a designated "
                                f"sync point"))
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item" and not node.args:
                if syncy(node.func.value):
                    out.append(Finding(
                        file=path, line=node.lineno, rule="trace-host-sync",
                        message=f".item() scalarizes a jax value in "
                                f"{fn.name}() — a blocking device "
                                f"round-trip"))

    # ---- traced-scope rules --------------------------------------------
    for sc in _collect_scopes(tree):
        fn = sc.node
        if isinstance(fn, ast.Lambda):
            traced = set(sc.traced_params)
            nodes = list(ast.walk(fn.body))
            tests: List[ast.AST] = [n for n in nodes
                                    if isinstance(n, ast.IfExp)]
        else:
            traced = _traced_names_flow(fn, sc.traced_params)
            nodes = list(_own_nodes(fn))
            tests = [n for n in nodes if isinstance(n, (ast.If, ast.IfExp))]

        # numpy on traced values
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            p = _attr_path(node.func)
            if not p or p[0] not in _NP_ROOTS:
                continue
            arg_names: Set[str] = set()
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                arg_names |= _value_names(a)
            if arg_names & traced:
                out.append(Finding(
                    file=path, line=node.lineno,
                    rule="trace-numpy-on-traced",
                    message=f"np.{'.'.join(p[1:])}() applied to traced "
                            f"value(s) {sorted(arg_names & traced)} inside "
                            f"jit-traced scope "
                            f"{getattr(fn, 'name', '<lambda>')} ({sc.why}) "
                            f"— use jnp, or hoist to the host boundary"))

        # python branch on traced values
        for node in tests:
            test = node.test
            if _exempt_test(test):
                continue
            names = _value_names(test)
            if (names & traced) or _contains_jax_call(test):
                out.append(Finding(
                    file=path, line=node.lineno, rule="trace-python-branch",
                    message=f"Python branch on traced value(s) "
                            f"{sorted((names & traced)) or '(jnp expr)'} "
                            f"inside jit-traced scope "
                            f"{getattr(fn, 'name', '<lambda>')} ({sc.why}) "
                            f"— use jnp.where / lax.cond"))

    return apply_pragmas(out, allowed)


def check_tree(root: str, subdir: str = "") -> List[Finding]:
    """Lint every ``.py`` under ``root`` (a package dir, e.g. src/repro)."""
    out: List[Finding] = []
    base = os.path.join(root, subdir) if subdir else root
    for dirpath, _, files in sorted(os.walk(base)):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                src = f.read()
            rel = os.path.relpath(path, os.path.dirname(root))
            out.extend(check_source(src, rel))
    return out
