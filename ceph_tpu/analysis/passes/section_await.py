"""section-await: a layer section that spans a suspension point.

A layer section (`common/tracer.py` `section()` / `layer_section()`)
times synchronous work on one thread.  Every daemon of a process shares
one asyncio loop, so an ``await`` inside the section's block hands the
loop to other tasks, and the section then times their work as its own;
their sections on the same thread also nest under it and corrupt its
self time.  The pass flags ``await``, ``async with`` and ``async for``
written directly inside a ``with section(...)`` block (not inside a
function defined there, which does not run in the block), and
``@sectioned(...)`` on an ``async def``.
"""

from __future__ import annotations

import ast

from .. import Finding, SourceTree

_SECTION_CALLS = {"section", "layer_section"}
_SUSPENDS = {ast.Await: "await", ast.AsyncWith: "async with",
             ast.AsyncFor: "async for"}
_NEW_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)


def _callee(node: ast.AST) -> str:
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Attribute):
            return fn.attr
        if isinstance(fn, ast.Name):
            return fn.id
    return ""


def _suspensions(stmts):
    """Suspension points in `stmts`, not descending into new scopes."""
    todo = [s for s in stmts if not isinstance(s, _NEW_SCOPES)]
    while todo:
        node = todo.pop()
        kind = _SUSPENDS.get(type(node))
        if kind is not None:
            yield node, kind
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, _NEW_SCOPES):
                todo.append(child)


class SectionAwaitPass:
    PASS_ID = "section-await"
    DESCRIBE = (
        "await / async with / async for inside a `with section(...)` "
        "block, or @sectioned on an async def: the layer section would "
        "time other tasks' work on the shared loop"
    )

    def __call__(self, tree: SourceTree) -> list[Finding]:
        findings: list[Finding] = []
        for sf in tree.files:
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.With) and any(
                    _callee(item.context_expr) in _SECTION_CALLS
                    for item in node.items
                ):
                    for sub, kind in _suspensions(node.body):
                        findings.append(self._finding(sf, sub, kind))
                elif isinstance(node, ast.AsyncFunctionDef) and any(
                    _callee(d) == "sectioned" for d in node.decorator_list
                ):
                    # keyed by the coroutine's own scope, like the rest
                    findings.append(self._finding(sf, node.body[0], "async def"))
        return findings

    def _finding(self, sf, node: ast.AST, kind: str) -> Finding:
        return Finding(
            pass_id=self.PASS_ID,
            file=sf.rel,
            line=node.lineno,
            key=f"{sf.rel}::{sf.scope_of(node)}::{kind}",
            message=(
                f"{kind} inside a layer section: the loop runs other "
                "tasks while the section is open, so it would time "
                "their work as this layer's; close the section before "
                "suspending"
            ),
        )
