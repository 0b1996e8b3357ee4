"""jit-purity checker: device kernels must stay traceable (PR 1/PR 2).

Incidents: the device path falls back to the host Evaluator on ANY kernel
exception (PR 1's breaker), so an impure jitted function does not crash —
it silently pins the slow path. And a function that mutates Python state
under trace bakes the first call's value into the compiled executable
(classic jax footgun), which the equivalence fuzz only catches when the
divergence is visible in assignments.

Rules (any function reaching ``jax.jit``/``pjit`` — decorated directly,
via ``partial(jax.jit, ...)``, passed to a ``jit(...)`` call, or called
(transitively, same module) from such a function — helpers called from a
jitted function are traced exactly like their caller):

- ``no-global-mutation``: no ``global``/``nonlocal`` declarations inside a
  traced function;
- ``no-attr-assign``: no assignment to object attributes (mutating
  closed-over/carried Python objects under trace);
- ``no-impure-call``: no calls to impure builtins (print/open/input/exec/
  eval/breakpoint) or host-state modules (time/os/random/sys) — use
  ``jax.debug.print`` for traced debugging;
- ``donated-buffer-reuse``: an argument donated via ``donate_argnums``
  must not be read again after the call in the same scope (the buffer is
  dead; XLA may have aliased it into the output).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from .base import Checker, Finding, ModuleSource, attr_chain, register

IMPURE_BUILTINS = {"print", "open", "input", "exec", "eval", "breakpoint"}
IMPURE_MODULES = {"time", "os", "random", "sys"}


def _is_jit_expr(node: ast.AST) -> bool:
    """`jax.jit`, `jit`, `pjit`, `jax.pjit` as a bare expression."""
    chain = attr_chain(node)
    return bool(chain) and chain[-1] in ("jit", "pjit")


def _jit_wrap_target(call: ast.Call) -> Optional[str]:
    """For `jax.jit(fn, ...)` / `pjit(fn, ...)` / `shard_map(fn, ...)`:
    the wrapped function name. shard_map BODIES run under trace exactly
    like jitted functions (ISSUE 15: the explicit mesh lap kernel), so
    they join the purity scan scope — and, transitively, index-dtype's."""
    chain = attr_chain(call.func)
    is_wrap = (_is_jit_expr(call.func)
               or (bool(chain) and chain[-1] == "shard_map"))
    if is_wrap and call.args and isinstance(call.args[0], ast.Name):
        return call.args[0].id
    return None


def _decorator_is_jit(dec: ast.AST) -> bool:
    if _is_jit_expr(dec):
        return True
    if isinstance(dec, ast.Call):
        if _is_jit_expr(dec.func):  # @jax.jit(static_argnames=...)
            return True
        chain = attr_chain(dec.func)  # @partial(jax.jit, ...)
        if chain and chain[-1] == "partial" and dec.args:
            return _is_jit_expr(dec.args[0])
    return False


def _donate_argnums(call_or_dec: ast.AST) -> Optional[Set[int]]:
    """Statically-known donate_argnums of a jit(...) / partial(jax.jit, ...)
    expression; None when absent or not a constant."""
    if not isinstance(call_or_dec, ast.Call):
        return None
    for kw in call_or_dec.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return {v.value}
        if isinstance(v, (ast.Tuple, ast.List)) and all(
                isinstance(e, ast.Constant) and isinstance(e.value, int)
                for e in v.elts):
            return {e.value for e in v.elts}
        return None
    return None


def jit_reachable_functions(tree: ast.AST) -> List[ast.FunctionDef]:
    """Every function def that can run UNDER TRACE: decorated with jit/
    pjit (incl. partial(jax.jit, ...)), wrapped via ``jit(fn)``, or
    transitively called (same module) from one that is. Shared with the
    span-discipline checker — span/metric calls are host-state effects and
    must never appear inside these (ISSUE 8 composition seam). The result
    is memoized ON the tree object: both checkers visit every module of
    the package, and the reachability walk is the expensive part."""
    memo = getattr(tree, "_jit_reachable_memo", None)
    if memo is not None:
        return memo
    result = _jit_reachable_uncached(tree)
    try:
        tree._jit_reachable_memo = result
    except AttributeError:
        pass  # non-Module roots (fixtures) may not accept attributes
    return result


def _jit_reachable_uncached(tree: ast.AST) -> List[ast.FunctionDef]:
    defs: Dict[str, List[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, []).append(node)

    jit_fns: List[ast.FunctionDef] = []
    jit_ids: Set[int] = set()  # id()-keyed membership (no O(n) list scans)

    def _add(fn: ast.FunctionDef) -> None:
        if id(fn) not in jit_ids:
            jit_ids.add(id(fn))
            jit_fns.append(fn)

    for fns in defs.values():
        for fn in fns:
            if any(_decorator_is_jit(dec) for dec in fn.decorator_list):
                _add(fn)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            target = _jit_wrap_target(node)
            if target and target in defs:
                for f in defs[target]:
                    _add(f)

    # Transitive closure over same-module calls: a helper called from a
    # jitted function is traced exactly like its caller (kernel helpers
    # hold most of the actual math in ops/kernel.py).
    reached = {fn.name for fn in jit_fns}
    frontier = set(reached)
    while frontier:
        nxt = set()
        for name in frontier:
            for fn in defs.get(name, ()):
                # the body only: a decorator runs once, where the function
                # is defined, never under trace
                for c in (n for stmt in fn.body for n in ast.walk(stmt)):
                    if isinstance(c, ast.Call):
                        chain = attr_chain(c.func)
                        if (len(chain) == 1 and chain[0] in defs
                                and chain[0] not in reached):
                            nxt.add(chain[0])
        reached |= nxt
        frontier = nxt
    for name in reached:
        for f in defs[name]:
            _add(f)
    return jit_fns


@register
class JitPurityChecker(Checker):
    id = "jit-purity"
    description = ("functions reaching jax.jit/pjit must not mutate host "
                   "state; donated buffers must not be reused after the "
                   "call")

    def check(self, mod: ModuleSource) -> List[Finding]:
        out: List[Finding] = []
        tree = mod.tree
        defs: Dict[str, List[ast.FunctionDef]] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef):
                defs.setdefault(node.name, []).append(node)

        donated_defs: Dict[str, Set[int]] = {}  # decorated fns w/ donation
        for name, fns in defs.items():
            for fn in fns:
                for dec in fn.decorator_list:
                    if _decorator_is_jit(dec):
                        don = _donate_argnums(dec)
                        if don:
                            donated_defs[name] = don
                        break

        for fn in jit_reachable_functions(tree):
            out.extend(self._check_purity(mod, fn))

        # Donation discipline: per enclosing scope, a name bound to
        # jit(..., donate_argnums=...) — or a call to a donation-decorated
        # def — must not have its donated args read after the call.
        scopes = [tree] + [n for n in ast.walk(tree)
                           if isinstance(n, ast.FunctionDef)]
        for scope in scopes:
            out.extend(self._check_donation(mod, scope, donated_defs))
        return out

    # -- purity -------------------------------------------------------------

    def _check_purity(self, mod: ModuleSource,
                      fn: ast.FunctionDef) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(fn):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                kind = "global" if isinstance(node, ast.Global) else "nonlocal"
                out.append(Finding(
                    self.id, "no-global-mutation", mod.path, node.lineno,
                    f"`{kind} {', '.join(node.names)}` inside jitted "
                    f"{fn.name}: host-state writes are baked in at trace "
                    "time, not executed per call"))
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Attribute):
                    out.append(Finding(
                        self.id, "no-attr-assign", mod.path, node.lineno,
                        f"attribute assignment inside jitted {fn.name} "
                        "mutates a Python object under trace"))
            if isinstance(node, ast.Call):
                chain = attr_chain(node.func)
                if (isinstance(node.func, ast.Name)
                        and node.func.id in IMPURE_BUILTINS):
                    out.append(Finding(
                        self.id, "no-impure-call", mod.path, node.lineno,
                        f"call to impure builtin {node.func.id}() inside "
                        f"jitted {fn.name} (use jax.debug.* for traced "
                        "debugging)"))
                elif len(chain) >= 2 and chain[0] in IMPURE_MODULES:
                    out.append(Finding(
                        self.id, "no-impure-call", mod.path, node.lineno,
                        f"call to {'.'.join(chain)} inside jitted {fn.name} "
                        "reads/writes host state under trace"))
        return out

    # -- donation -----------------------------------------------------------

    def _check_donation(self, mod: ModuleSource, scope: ast.AST,
                        donated_defs: Dict[str, Set[int]]) -> List[Finding]:
        out: List[Finding] = []
        body = scope.body if hasattr(scope, "body") else []
        donated_callables: Dict[str, Set[int]] = dict(donated_defs)
        # `g = jax.jit(f, donate_argnums=...)` bound in this scope
        for stmt in body:
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and isinstance(stmt.value, ast.Call)
                    and _is_jit_expr(stmt.value.func)):
                don = _donate_argnums(stmt.value)
                if don:
                    donated_callables[stmt.targets[0].id] = don

        if not donated_callables:
            return out

        # Find calls to donated callables directly in this scope (not in
        # nested defs — those are their own scope pass).
        def iter_scope_nodes(node):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    continue  # nested scopes get their own donation pass
                yield child
                yield from iter_scope_nodes(child)

        scope_nodes = list(iter_scope_nodes(scope))
        rebinds: Dict[str, List[int]] = {}
        loads: Dict[str, List[int]] = {}
        for n in scope_nodes:
            if isinstance(n, ast.Name):
                if isinstance(n.ctx, ast.Store):
                    rebinds.setdefault(n.id, []).append(n.lineno)
                elif isinstance(n.ctx, ast.Load):
                    loads.setdefault(n.id, []).append(n.lineno)
        for n in scope_nodes:
            if not (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                    and n.func.id in donated_callables):
                continue
            for pos in donated_callables[n.func.id]:
                if pos >= len(n.args) or not isinstance(n.args[pos], ast.Name):
                    continue
                arg = n.args[pos].id
                # >= : `a = g(a)` rebinds the donated name on the call line
                # itself, shielding every later load.
                next_rebind = min(
                    (ln for ln in rebinds.get(arg, ()) if ln >= n.lineno),
                    default=None)
                for ln in loads.get(arg, ()):
                    if ln > n.lineno and (next_rebind is None
                                          or ln < next_rebind):
                        out.append(Finding(
                            self.id, "donated-buffer-reuse", mod.path, ln,
                            f"`{arg}` is donated to {n.func.id} (line "
                            f"{n.lineno}) but read again here — the buffer "
                            "may be aliased into the output"))
        return out
