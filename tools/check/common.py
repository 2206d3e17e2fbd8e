"""Shared plumbing for the repro-lint checkers.

A checker is a function that returns a list of :class:`Finding`; the CLI
(`python -m tools.check`) concatenates them and exits nonzero when any
survive. Findings carry a stable ``rule`` id (``HS...`` host-sync,
``SR...`` semiring registry, ``PL...`` pallas resources, ``OD...`` options
drift) so the fixture self-tests can assert exact rule/line pairs.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Finding:
    """One checker violation, anchored to a file/line."""

    checker: str   # "host-sync" | "semiring" | "pallas" | "options"
    rule: str      # stable id, e.g. "HS001"
    path: str      # repo-relative when produced by run_all
    line: int      # 1-based; 0 = whole-file / registry-level finding
    message: str
    end_line: int = 0  # last line of the flagged expression (0 = same line)

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        return f"{loc}: {self.rule} [{self.checker}] {self.message}"


# ---------------------------------------------------------------- pragmas

# `# repro: allow-host-sync(reason)` — suppresses host-sync findings on its
# line. The reason is mandatory: a pragma is an audit record, not a mute.
_PRAGMA_RE = re.compile(r"#\s*repro:\s*allow-host-sync\(([^)]*)\)")


def parse_pragmas(source: str) -> dict[int, str]:
    """Map 1-based line number -> pragma reason (may be empty string)."""
    out: dict[int, str] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _PRAGMA_RE.search(text)
        if m:
            out[i] = m.group(1).strip()
    return out


def apply_pragmas(
    findings: list[Finding], pragmas: dict[int, str], path: str
) -> list[Finding]:
    """Drop findings on pragma'd lines; flag pragmas with no reason.

    A pragma covers a finding when it sits on *any* line of the flagged
    expression (multi-line calls put the comment wherever it reads best).
    """

    def covered(f: Finding) -> bool:
        hi = max(f.line, f.end_line)
        return any(ln in pragmas for ln in range(f.line, hi + 1))

    kept = [f for f in findings if not covered(f)]
    for line, reason in pragmas.items():
        if not reason:
            kept.append(Finding(
                "host-sync", "HS006", path, line,
                "allow-host-sync pragma without a reason; pragmas are audit "
                "records — say what transfers and why it is acceptable",
            ))
    return kept


# ------------------------------------------------- safe shape arithmetic

_BINOPS = {
    ast.Add: lambda a, b: a + b,
    ast.Sub: lambda a, b: a - b,
    ast.Mult: lambda a, b: a * b,
    ast.FloorDiv: lambda a, b: a // b,
    ast.Div: lambda a, b: a / b,
    ast.Mod: lambda a, b: a % b,
    ast.Pow: lambda a, b: a ** b,
}


class ShapeEvalError(Exception):
    """A shape expression references something outside the point env."""


def eval_shape_expr(node: ast.AST, env: dict):
    """Evaluate a BlockSpec/scratch shape expression at a budget point.

    Supports the arithmetic subset shapes are written in — constants (None:
    a squeezed block dimension, size 1), env names, + - * // / % **, tuples,
    unary minus, and min/max calls. Anything else raises
    :class:`ShapeEvalError` so the checker can report the expression as
    statically unresolvable instead of guessing.
    """
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
        return node.value
    if isinstance(node, ast.Constant) and node.value is None:
        return 1  # a squeezed block dimension holds one element
    if isinstance(node, ast.Name):
        if node.id in env:
            return env[node.id]
        raise ShapeEvalError(f"unknown dimension name {node.id!r}")
    if isinstance(node, ast.Tuple):
        return tuple(eval_shape_expr(e, env) for e in node.elts)
    if isinstance(node, ast.BinOp) and type(node.op) in _BINOPS:
        return _BINOPS[type(node.op)](
            eval_shape_expr(node.left, env), eval_shape_expr(node.right, env)
        )
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -eval_shape_expr(node.operand, env)
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id in ("min", "max") and not node.keywords):
        vals = [eval_shape_expr(a, env) for a in node.args]
        return (min if node.func.id == "min" else max)(vals)
    raise ShapeEvalError(
        f"unsupported shape expression {ast.dump(node)[:80]}"
    )


def attr_chain(node: ast.AST) -> Optional[str]:
    """``jax.experimental.pallas`` -> "jax.experimental.pallas"; None when
    the expression is not a pure dotted name."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None
