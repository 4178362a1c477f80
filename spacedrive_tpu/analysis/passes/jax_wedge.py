"""jax-wedge-safety: every production jax device touchpoint must be
dominated by the device gate.

``utils/jax_guard.ensure_jax_safe()`` is the one place a production path
asks whether it has an accelerator before its first device use; a path
that skips it decides its device route somewhere nobody can see. This
pass keeps every touchpoint behind that one gate.

What counts as the device surface (first touch inits the backend):
- ``jax.devices(...)`` / ``jax.device_put(...)`` call sites (module alias
  or ``from jax import ...`` name);
- ``jit(...)(...)``: calling a freshly-jitted function;
- any ``jax``/``jax.numpy`` attribute use at module import time (an
  import-time jnp op wedges on *import*, before any guard can run).

What counts as a guard: a call to ``ensure_jax_safe`` (any spelling).

Domination is approximated lexically (guard call on an earlier line of
the same function), plus two helper forms the codebase actually uses:
- a nested function defined after the guard ran in its enclosing scope;
- a module-local helper whose every module-internal call site is itself
  guard-dominated (transitively) — e.g. ``_signatures`` in
  objects/dedup.py, called only after ``find_near_duplicates`` guarded.
A helper nobody in the module calls gets no benefit of the doubt: it is
a public entry point and must guard itself.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..engine import AnalysisPass, FileContext, Finding, dotted_name

#: subsystems where an unguarded touchpoint can wedge production workers
PRODUCTION_DIRS = ("jobs", "objects", "locations", "api", "server")

#: jax attributes whose call is the device surface
SURFACE_ATTRS = ("devices", "device_put")


class _Bindings:
    """Module import map: which local names reach jax, and which are guards."""

    def __init__(self, tree: ast.Module) -> None:
        self.jax_roots: set[str] = set()      # names bound to jax/jax.numpy
        self.surface_funcs: dict[str, str] = {}  # local name -> jax.<attr>
        self.jit_names: set[str] = set()      # local names for jax.jit
        self.guard_names: set[str] = set()    # ensure_jax_safe aliases
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "jax" or alias.name.startswith("jax."):
                        self.jax_roots.add(
                            alias.asname or alias.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "jax" or mod.startswith("jax."):
                    for alias in node.names:
                        local = alias.asname or alias.name
                        if alias.name in SURFACE_ATTRS:
                            self.surface_funcs[local] = f"jax.{alias.name}"
                        elif alias.name == "jit":
                            self.jit_names.add(local)
                        elif alias.name == "numpy":
                            self.jax_roots.add(local)
                elif mod.endswith("jax_guard"):
                    for alias in node.names:
                        if alias.name == "ensure_jax_safe":
                            self.guard_names.add(alias.asname or alias.name)

    # -- classification ------------------------------------------------------
    def surface_call(self, call: ast.Call) -> str | None:
        """Device-surface description for this call site, or None."""
        d = dotted_name(call.func)
        if d is not None:
            parts = d.split(".")
            if (len(parts) > 1 and parts[0] in self.jax_roots
                    and parts[-1] in SURFACE_ATTRS):
                return f"jax.{parts[-1]}()"
            if d in self.surface_funcs:
                return f"{self.surface_funcs[d]}()"
        if isinstance(call.func, ast.Call):  # jit(...)(...)
            inner = dotted_name(call.func.func)
            if inner is not None:
                parts = inner.split(".")
                # either an aliased `from jax import jit as X` name, or a
                # dotted jax.jit/jnp-root spelling
                if (inner in self.jit_names
                        or (parts[-1] == "jit"
                            and parts[0] in self.jax_roots)):
                    return "jit(...)(...)"
        return None

    def guard_call(self, call: ast.Call) -> bool:
        d = dotted_name(call.func)
        if d is None:
            return False
        parts = d.split(".")
        if parts[-1] == "ensure_jax_safe":
            return True
        return d in self.guard_names

    def jax_touch(self, node: ast.AST) -> bool:
        """Any expression reaching a jax-bound name (module-level check)."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute):
                d = dotted_name(sub)
                if d is not None and d.split(".")[0] in self.jax_roots:
                    return True
        return False


class _FuncInfo:
    __slots__ = ("name", "node", "surfaces", "guards", "calls",
                 "inherited_guard")

    def __init__(self, name: str, node: ast.AST) -> None:
        self.name = name
        self.node = node
        self.surfaces: list[tuple[int, str]] = []   # (lineno, description)
        self.guards: list[int] = []                 # guard-call linenos
        self.calls: list[tuple[str, int]] = []      # (callee name, lineno)
        #: nested function defined after its enclosing scope already guarded
        self.inherited_guard = False


class JaxWedgePass(AnalysisPass):
    id = "jax-wedge"
    description = ("jax device touchpoints in production modules not "
                   "dominated by ensure_jax_safe()")

    def run(self, ctx: FileContext) -> Iterator[Finding]:
        if not ctx.in_dirs(*PRODUCTION_DIRS):
            return
        bindings = _Bindings(ctx.tree)
        # cheap bail: module never names jax at all
        if not (bindings.jax_roots or bindings.surface_funcs
                or bindings.jit_names):
            return

        yield from self._module_level(ctx, bindings)

        funcs: list[_FuncInfo] = []
        self._collect(ctx.tree.body, bindings, funcs)
        module_funcs = {f.name: f for f in funcs
                        if isinstance(ctx.parent(f.node), ast.Module)}
        guarded_entry = self._propagate(funcs, module_funcs)

        for info in funcs:
            entry_guarded = info.inherited_guard or (
                module_funcs.get(info.name) is info
                and guarded_entry.get(info.name, False))
            for lineno, desc in info.surfaces:
                if entry_guarded:
                    continue
                if any(g < lineno for g in info.guards):
                    continue
                yield ctx.finding(
                    lineno, self.id,
                    f"unguarded jax device access ({desc}) in "
                    f"'{info.name}' — call ensure_jax_safe() earlier in "
                    "this function, or guard every call site of it")

    # -- module import time --------------------------------------------------
    def _module_level(self, ctx: FileContext,
                      bindings: _Bindings) -> Iterator[Finding]:
        for stmt in ctx.tree.body:
            if isinstance(stmt, (ast.Import, ast.ImportFrom,
                                 ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if bindings.jax_touch(stmt):
                yield ctx.finding(
                    stmt.lineno, self.id,
                    "jax use at module import time — importing this module "
                    "can init the (possibly wedged) device backend before "
                    "any guard runs; move it into a guarded function")

    # -- per-function collection --------------------------------------------
    def _collect(self, body: list[ast.stmt], bindings: _Bindings,
                 out: list[_FuncInfo]) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info = _FuncInfo(stmt.name, stmt)
                out.append(info)
                self._scan_function(stmt, bindings, info, out)
            elif isinstance(stmt, ast.ClassDef):
                self._collect(stmt.body, bindings, out)

    def _scan_function(self, func: ast.AST, bindings: _Bindings,
                       info: _FuncInfo, out: list[_FuncInfo]) -> None:
        """Walk one function's own nodes in source order; nested defs (at
        any statement depth) become separate _FuncInfo scopes so their
        touchpoints are judged against their own guards."""

        def visit(node: ast.AST) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                nested = _FuncInfo(node.name, node)
                if info.inherited_guard or any(
                        g < node.lineno for g in info.guards):
                    nested.inherited_guard = True
                out.append(nested)
                self._scan_function(node, bindings, nested, out)
                return
            if isinstance(node, ast.Call):
                if bindings.guard_call(node):
                    info.guards.append(node.lineno)
                else:
                    desc = bindings.surface_call(node)
                    if desc is not None:
                        info.surfaces.append((node.lineno, desc))
                    callee = dotted_name(node.func)
                    if callee is not None and "." not in callee:
                        info.calls.append((callee, node.lineno))
            for child in ast.iter_child_nodes(node):
                visit(child)

        for stmt in getattr(func, "body", []):
            visit(stmt)

    # -- interprocedural (module-local) guard propagation --------------------
    def _propagate(self, funcs: list[_FuncInfo],
                   module_funcs: dict[str, _FuncInfo]) -> dict[str, bool]:
        """Fixpoint: a module-level helper is guarded-on-entry when every
        module-internal call site of it is guard-dominated. No call sites →
        public entry point → not guarded."""
        call_sites: dict[str, list[tuple[_FuncInfo, int]]] = {}
        for caller in funcs:
            for callee, lineno in caller.calls:
                if callee in module_funcs:
                    call_sites.setdefault(callee, []).append((caller, lineno))

        guarded = {name: False for name in module_funcs}
        changed = True
        while changed:
            changed = False
            for name, info in module_funcs.items():
                if guarded[name]:
                    continue
                sites = call_sites.get(name)
                if not sites:
                    continue
                if all(caller.inherited_guard
                       or any(g < lineno for g in caller.guards)
                       or guarded.get(caller.name, False)
                       for caller, lineno in sites):
                    guarded[name] = True
                    changed = True
        return guarded
