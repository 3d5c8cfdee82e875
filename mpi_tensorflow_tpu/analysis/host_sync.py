"""host-sync pass: no accidental device->host syncs in the hot loop.

Every ``int()`` / ``float()`` / ``bool()`` / ``.item()`` /
``np.asarray()`` on the result of a jitted call blocks the host on the
device stream.  The serving hot loop (``serving/iteration.py``, the
engine's step path, the router's tick path) budgets its syncs — one
bulk ``np.asarray`` per dispatch — and anything beyond that is latency
the continuous-batching design exists to avoid.

The pass taints names bound from calls through jit-built attributes
(``self._decode_fn = jax.jit(...)`` and friends) and flags host
conversions applied to tainted values inside the hot namespace.
Intended syncs carry ``# graft-lint: sync-ok(<reason>)`` on the line
or the line above; ``.item()`` is flagged unconditionally (the
per-element sync pattern has no place in the hot loop).

The taint follows a value through the object that keeps it: an
attribute a hot function assigns a tainted value to, or appends one to
(``self._unread.append((nxt, rows))``), HOLDS device values, and what
another hot function binds from it (``for nxt, rows in
self._unread[:n]``) is tainted there; a tainted argument of a call to
another hot method taints that method's parameter.  That is what lets
the engine's one-step lookahead be pinned: the dispatching functions
carry no ``sync-ok`` at all, and the one read a dispatch sits where the
previous dispatch is delivered.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from mpi_tensorflow_tpu.analysis import core

PASS_IDS = ("HOST-SYNC",)

JIT_CTORS = {"jax.jit", "jit", "pjit", "jax.pjit"}
#: hot namespace: path suffix -> function names (None = whole file)
HOT: Dict[str, Optional[Set[str]]] = {
    "serving/iteration.py": None,
    "serving/engine.py": {"step", "_advance_prefill", "_dispatch_decode",
                          "_count_dispatch", "_hold", "_deliver",
                          "_log_dispatch", "_read_counters",
                          "_step_verify",
                          "_ensure_private", "_track_occupancy"},
    "serving/router.py": {"route", "load_score", "_tick", "_route_due",
                          "_observe_fleet"},
}
HOST_CASTS = {"int", "float", "bool"}
HOST_COPIES = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


def _device_attrs(tree: ast.Module) -> Set[str]:
    """Attribute names assigned from ``jax.jit(...)`` anywhere in the
    module (``self._decode_fn = jax.jit(self._decode_impl, ...)``)."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and core.dotted_name(node.value.func) in JIT_CTORS:
            for t in node.targets:
                if isinstance(t, ast.Attribute):
                    out.add(t.attr)
    return out


def _hot_functions(rel: str, tree: ast.Module):
    for suffix, names in HOT.items():
        if rel.endswith(suffix):
            for fn in core.iter_functions(tree):
                if names is None or fn.name in names:
                    yield fn
            return


class _FnChecker:
    """Statement-ordered taint walk of one hot function.  ``holding``
    (attribute names that keep device values) and ``params`` (hot
    function name -> parameter names a caller passed a tainted value
    for) are the module's, shared by its hot functions and grown here."""

    def __init__(self, rel: str, src: str, device_attrs: Set[str],
                 findings: List[core.Finding], holding: Set[str],
                 params: Dict[str, Set[str]],
                 hot: Dict[str, ast.AST]):
        self.rel = rel
        self.src = src
        self.device_attrs = device_attrs
        self.findings = findings
        self.holding = holding
        self.params = params
        self.hot = hot
        self.tainted: Set[str] = set()

    # -- taint helpers --

    def _is_device_call(self, node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in self.device_attrs)

    def _is_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            return node.attr in self.holding
        if isinstance(node, ast.Subscript):
            return self._is_tainted(node.value)
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "pop":
            return self._is_tainted(node.func.value)    # self._held.pop(0)
        return False

    def _carries(self, node: ast.AST) -> bool:
        """``node`` is, or is a tuple/list display that mentions, a
        tainted value."""
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self._carries(e) for e in node.elts)
        return self._is_tainted(node) or self._is_device_call(node)

    def _flag(self, node: ast.AST, what: str) -> None:
        if core.allowlist_reason(self.src, node.lineno, "sync"):
            return
        self.findings.append(core.Finding(
            self.rel, node.lineno, "HOST-SYNC",
            f"{what} forces a device->host sync in the hot loop "
            f"(batch it, hoist it, or annotate "
            f"`# graft-lint: sync-ok(<reason>)`)"))

    # -- expression scan (uses) --

    def check_expr(self, node: ast.AST) -> None:
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            name = core.dotted_name(sub.func)
            if name in HOST_CASTS and sub.args \
                    and self._is_tainted(sub.args[0]):
                self._flag(sub, f"{name}() on a jitted-call result")
            elif name in HOST_COPIES and sub.args \
                    and self._is_tainted(sub.args[0]):
                self._flag(sub, f"{name}() on a jitted-call result")
            elif isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr == "item" and not sub.args:
                self._flag(sub, ".item()")
            elif isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr == "append" \
                    and isinstance(sub.func.value, ast.Attribute) \
                    and any(self._carries(a) for a in sub.args):
                self.holding.add(sub.func.value.attr)
            elif isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in self.hot:
                # a tainted argument taints the hot callee's parameter
                names = core.arg_names(self.hot[sub.func.attr])
                for name, arg in zip(names, sub.args):
                    if self._carries(arg):
                        self.params.setdefault(sub.func.attr,
                                               set()).add(name)

    # -- statement walk (flow order: check uses, then bind) --

    def run(self, fn: ast.AST) -> None:
        self.tainted = set(self.params.get(fn.name, ()))
        self.visit_body(fn.body)

    def visit_body(self, body) -> None:
        for stmt in body:
            self.visit_stmt(stmt)

    def _bind(self, targets, value) -> None:
        names, attrs = [], []
        for t in targets:
            for e in (t.elts if isinstance(t, (ast.Tuple, ast.List))
                      else [t]):
                if isinstance(e, ast.Name):
                    names.append(e.id)
                elif isinstance(e, ast.Attribute):
                    attrs.append(e.attr)
        if self._carries(value):
            self.tainted |= set(names)
            self.holding |= set(attrs)
        else:
            self.tainted -= set(names)

    def visit_stmt(self, stmt: ast.AST) -> None:
        if isinstance(stmt, ast.Assign):
            self.check_expr(stmt.value)
            self._bind(stmt.targets, stmt.value)
        elif isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self.check_expr(stmt.value)
                self._bind([stmt.target], stmt.value)
        elif isinstance(stmt, ast.AugAssign):
            self.check_expr(stmt.value)
        elif isinstance(stmt, (ast.Expr, ast.Return)):
            if getattr(stmt, "value", None) is not None:
                self.check_expr(stmt.value)
        elif isinstance(stmt, (ast.If, ast.While)):
            self.check_expr(stmt.test)
            self.visit_body(stmt.body)
            self.visit_body(stmt.orelse)
        elif isinstance(stmt, ast.For):
            self.check_expr(stmt.iter)
            self._bind([stmt.target], stmt.iter)
            self.visit_body(stmt.body)
            self.visit_body(stmt.orelse)
        elif isinstance(stmt, ast.With):
            for item in stmt.items:
                self.check_expr(item.context_expr)
            self.visit_body(stmt.body)
        elif isinstance(stmt, ast.Try):
            self.visit_body(stmt.body)
            for handler in stmt.handlers:
                self.visit_body(handler.body)
            self.visit_body(stmt.orelse)
            self.visit_body(stmt.finalbody)
        elif isinstance(stmt, (ast.Raise, ast.Assert)):
            for val in (getattr(stmt, "exc", None),
                        getattr(stmt, "test", None),
                        getattr(stmt, "msg", None)):
                if val is not None:
                    self.check_expr(val)
        # nested defs start a fresh scope; hot-ness is per named
        # function, so nested bodies are skipped here


def run(sources: Dict[str, str]) -> List[core.Finding]:
    findings: List[core.Finding] = []
    trees = core.parse_sources(sources)
    for rel, tree in trees.items():
        device_attrs = _device_attrs(tree)
        hot = {fn.name: fn for fn in _hot_functions(rel, tree)}
        holding: Set[str] = set()
        params: Dict[str, Set[str]] = {}
        while True:
            # to a fixed point: what one function stores, another reads
            before = (len(holding), sum(map(len, params.values())))
            found: List[core.Finding] = []
            for fn in hot.values():
                _FnChecker(rel, sources[rel], device_attrs, found,
                           holding, params, hot).run(fn)
            if before == (len(holding), sum(map(len, params.values()))):
                break
        findings.extend(found)
    return findings
