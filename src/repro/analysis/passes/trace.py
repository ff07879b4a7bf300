"""trace-schema: every ``last_trace`` key written is declared centrally.

``check_trace_complete`` can only prove a batch's trace complete if the
runtime checker and the code writing the trace agree on the key set, so
every key written into ``SearchService.last_trace`` (directly, through a
local later stored into it, or through a dict parameter named ``trace``)
must appear in ``repro.search.schema.TRACE_SCHEMA``.  So must every span
name: the first argument of a ``<recorder>.span(...)`` call is a string
literal declared in ``TRACE_SCHEMA["spans"]``, the block
``last_trace["spans"]`` reports them in; a ``<recorder>.tally(name,
field=n)`` call names a declared span too, and each field is one of
``SPAN_FIELDS``.  Counters that are
members of a completeness partition must additionally be written with
integer expressions — PR 7 accumulated ``any(...)`` bools into
``early_terminated``, which saturated the count at 1 per batch while
every partition still balanced.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Tuple

from repro.analysis.engine import LintPass
from repro.analysis.schema import Finding
from repro.search.schema import SPAN_FIELDS, TRACE_COUNTERS, TRACE_SCHEMA

ALL_TRACE_KEYS = frozenset().union(*TRACE_SCHEMA.values())
SPAN_NAMES = TRACE_SCHEMA["spans"]

_BOOLISH_CALLS = {"any", "all", "bool"}


def _is_boolish(node: ast.AST) -> bool:
    """Whether an expression is bool-valued on its face: comparisons,
    and/or chains, True/False literals, and any()/all()/bool() calls."""
    if isinstance(node, (ast.Compare, ast.BoolOp)):
        return True
    if isinstance(node, ast.Constant) and isinstance(node.value, bool):
        return True
    if (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id in _BOOLISH_CALLS
    ):
        return True
    return False


def _is_last_trace(node: ast.AST) -> bool:
    return isinstance(node, ast.Attribute) and node.attr == "last_trace"


def _const_keys(sub: ast.Subscript) -> List[str]:
    """String key(s) a subscript writes: a constant, or both arms of a
    conditional key like ``t["a" if ranked else "b"]``."""
    sl = sub.slice
    if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
        return [sl.value]
    if isinstance(sl, ast.IfExp):
        keys = []
        for arm in (sl.body, sl.orelse):
            if isinstance(arm, ast.Constant) and isinstance(arm.value, str):
                keys.append(arm.value)
        return keys
    return []


def _scopes(tree: ast.AST) -> Iterator[ast.AST]:
    yield tree  # module level
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _scope_walk(scope: ast.AST) -> Iterator[ast.AST]:
    """Walk a scope WITHOUT descending into nested function scopes (a
    name's binding to a trace block is per-function; the module-level
    sweep must not see a method's locals)."""
    stack = list(
        ast.iter_child_nodes(scope)
        if isinstance(scope, (ast.Module, ast.FunctionDef,
                              ast.AsyncFunctionDef))
        else [scope]
    )
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


class TraceSchemaPass(LintPass):
    id = "trace-schema"

    def run(self, tree: ast.AST, path: str, src: str) -> List[Finding]:
        out: List[Finding] = []
        for scope in _scopes(tree):
            out.extend(self._check_scope(scope, path))
        out.extend(self._check_spans(tree, path))
        return out

    def _check_spans(self, tree: ast.AST, path: str) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("span", "tally") and node.args):
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                out.append(self.finding(
                    path, node,
                    "span name is not a string literal; the trace schema "
                    "can only check a literal",
                ))
            elif arg.value not in SPAN_NAMES:
                out.append(self.finding(
                    path, node,
                    f"span {arg.value!r} is not declared in "
                    f"repro.search.schema.TRACE_SCHEMA['spans']",
                ))
            if node.func.attr == "tally":
                for kw in node.keywords:
                    if kw.arg not in SPAN_FIELDS:
                        out.append(self.finding(
                            path, node,
                            f"span field {kw.arg!r} is not declared in "
                            f"repro.search.schema.SPAN_FIELDS",
                        ))
        return out

    # -------------------------------------------------------------------
    def _check_scope(self, scope: ast.AST, path: str) -> List[Finding]:
        out: List[Finding] = []
        # block ("" = top level) each local name is bound to, discovered
        # from `X.last_trace = name` / `X.last_trace[key] = name` sinks
        bound: Dict[str, str] = {}
        if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for arg in (
                scope.args.posonlyargs + scope.args.args
                + scope.args.kwonlyargs
            ):
                if arg.arg == "trace":
                    bound["trace"] = "*"  # block unknown: union of all
        for node in _scope_walk(scope):
            if not isinstance(node, ast.Assign):
                continue
            for t in node.targets:
                if _is_last_trace(t) and isinstance(node.value, ast.Name):
                    bound[node.value.id] = ""
                elif (
                    isinstance(t, ast.Subscript)
                    and _is_last_trace(t.value)
                    and isinstance(node.value, ast.Name)
                ):
                    for key in _const_keys(t):
                        bound[node.value.id] = key
                elif _is_last_trace(node.value) and isinstance(t, ast.Name):
                    bound[t.id] = ""  # tr = self.last_trace

        def keyset(block: str):
            if block == "*":
                return ALL_TRACE_KEYS
            return TRACE_SCHEMA.get(block)

        def check_key(node: ast.AST, key: str, block: str) -> None:
            ks = keyset(block)
            if ks is not None and key not in ks:
                where = f"block {block!r}" if block not in ("", "*") else \
                    "the top level"
            else:
                return
            out.append(self.finding(
                path, node,
                f"trace key {key!r} written to {where} is not declared "
                f"in repro.search.schema.TRACE_SCHEMA",
            ))

        def check_counter(node: ast.AST, key: str, value: ast.AST) -> None:
            if key in TRACE_COUNTERS and _is_boolish(value):
                out.append(self.finding(
                    path, node,
                    f"partition counter {key!r} written with a bool-valued "
                    f"expression; use an integer count (the PR 7 "
                    f"`any(...)` accumulation bug class)",
                ))

        def check_dict_literal(d: ast.AST, block: str) -> None:
            if not isinstance(d, ast.Dict):
                return
            for k, v in zip(d.keys, d.values):
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    check_key(k, k.value, block)
                    check_counter(k, k.value, v)

        for node in _scope_walk(scope):
            value: Optional[ast.AST] = None
            targets: Tuple[ast.AST, ...] = ()
            if isinstance(node, ast.Assign):
                targets, value = tuple(node.targets), node.value
            elif isinstance(node, ast.AugAssign):
                targets, value = (node.target,), node.value
            for t in targets:
                # X.last_trace = {...} / name  (dict literal checked here,
                # name bindings were resolved in the first sweep)
                if _is_last_trace(t):
                    check_dict_literal(value, "")
                    continue
                if not isinstance(t, ast.Subscript):
                    continue
                if _is_last_trace(t.value):
                    for key in _const_keys(t):
                        check_key(t, key, "")
                        check_counter(t, key, value)
                        check_dict_literal(value, key)
                elif isinstance(t.value, ast.Name) and t.value.id in bound:
                    block = bound[t.value.id]
                    for key in _const_keys(t):
                        check_key(t, key, block)
                        check_counter(t, key, value)
            # name = {...} for a name later stored into last_trace
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Dict
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id in bound:
                        check_dict_literal(node.value, bound[t.id])
        return out
