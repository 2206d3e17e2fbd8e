"""Host-sync leak detector (rules HS001-HS006).

The device-residency contract (PR 6): engine hot paths keep state as jax
arrays; the only device->host transfers are the audited, pragma'd readouts
(ticket resolution, the per-batch ``(d,)`` accounting report, run
finalization). Anything else — a stray ``float()`` on a traced scalar, an
``np.asarray`` on a resident matrix, a truthiness test on an array — blocks
the dispatch stream on TPU and silently erodes the perf the kernels buy.

This is a flow-insensitive AST pass over the annotated hot-path modules. It
infers which expressions are *jax-bound*:

* calls rooted at a jax-module alias (``jnp.*``, ``jax.*``, ``pl.*``,
  ``pltpu.*``) — except ``jax.device_get``, whose result is host;
* calls to functions decorated ``@jax.jit`` / ``@partial(jax.jit, ...)``
  anywhere in the scanned set, and to the configured device-returning
  helpers (:data:`DEVICE_RETURNING_FUNCS`);
* names assigned from jax-bound expressions (tuple unpacking included);
* ``self.<attr>`` where any method of the class assigns that attribute a
  jax-bound value, and the session attributes every layer treats as
  device-resident (:data:`DEVICE_ATTRS`, e.g. ``fam.session.state``);
* methods/subscripts/arithmetic of jax-bound values. ``.shape``/``.dtype``
  and friends are metadata, not transfers.

and then flags the sink positions:

HS001  float()/int()/bool() on a jax-bound value (implicit D2H sync)
HS002  .item() on a jax-bound value
HS003  np.* call with a jax-bound argument
HS004  truthiness test (if/while/assert/and/or/not) on a jax-bound value
HS005  jax.device_get — *explicit*, but still a sync: every call site must
       carry a ``# repro: allow-host-sync(reason)`` pragma, so the full
       audited-transfer inventory is greppable from the pragmas alone
HS006  a pragma with an empty reason (from `common.apply_pragmas`)

False-negative bias is deliberate: unknown calls launder jaxiness, so the
checker stays quiet on host-only numpy code instead of crying wolf — the
runtime transfer guard (``EngineOptions.transfer_guard="disallow"``) is the
backstop that catches what static inference cannot see.
"""
from __future__ import annotations

import ast
import glob
import os
from typing import Iterable, Optional

from tools.check.common import Finding, apply_pragmas, attr_chain, parse_pragmas

CHECKER = "host-sync"

# Hot-path modules under the residency contract (repo-relative). The
# observability layer and every module with trace-recording hooks are in
# scope: a span attribute that implicitly coerces a jax array is exactly
# the hidden-D2H class this checker exists to catch.
HOT_PATH_GLOBS = (
    "src/repro/core/gograph.py",
    "src/repro/core/metric.py",
    "src/repro/engine/api.py",
    "src/repro/engine/async_block.py",
    "src/repro/engine/harness.py",
    "src/repro/engine/push.py",
    "src/repro/obs/*.py",
    "src/repro/serving/server.py",
    "src/repro/serving/stats.py",
    "src/repro/kernels/*.py",
)

# Functions that return device arrays but are not themselves @jax.jit
# (their jit boundary is nested or they return containers of jax arrays).
DEVICE_RETURNING_FUNCS = {
    "pack_algorithm",           # kernels.ops: dict of jnp operand arrays
    "swap_in_column_device",    # engine.harness: jitted column scatter inside
}

# Attribute names that are device-resident on session/family objects across
# module boundaries (AsyncBlockSession contract), so `fam.session.state`
# reads as jax-bound even where the session type is not inferable.
DEVICE_ATTRS = {"state", "col_done", "col_rounds", "dirty"}

# Array metadata — reading these is free, never a transfer.
METADATA_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes", "sharding"}

# jax.* calls whose result is host data (device_get is audited separately)
JAX_HOST_FUNCS = {"device_get", "default_backend"}

_JAX_ROOT_MODULES = ("jax", "jax.numpy", "jax.experimental.pallas",
                     "jax.experimental.pallas.tpu", "jax.lax")


def _module_aliases(tree: ast.Module) -> tuple[set[str], set[str]]:
    """(jax-rooted local names, numpy-rooted local names) for one module."""
    jax_names: set[str] = set()
    np_names: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                local = (a.asname or a.name).split(".")[0]
                if a.name == "numpy" or a.name.startswith("numpy."):
                    np_names.add(a.asname or local)
                elif a.name.split(".")[0] == "jax":
                    jax_names.add(a.asname or local)
        elif isinstance(node, ast.ImportFrom) and node.module:
            root = node.module.split(".")[0]
            for a in node.names:
                if root == "jax":
                    jax_names.add(a.asname or a.name)
                elif root == "numpy":
                    np_names.add(a.asname or a.name)
    return jax_names, np_names


def _is_jit_decorated(fn: ast.AST) -> bool:
    """@jax.jit / @jit / @partial(jax.jit, ...) / @functools.partial(...)."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        chain = attr_chain(target) or ""
        if chain.endswith("jit"):
            return True
        if chain.endswith("partial") and isinstance(dec, ast.Call):
            for arg in dec.args:
                if (attr_chain(arg) or "").endswith("jit"):
                    return True
    return False


def collect_jit_functions(trees: Iterable[ast.Module]) -> set[str]:
    """Names of jit-decorated functions across the whole scanned set, so
    `out = _run(...)` is jax-bound even across module boundaries."""
    out = set(DEVICE_RETURNING_FUNCS)
    for tree in trees:
        for node in ast.walk(tree):
            if _is_jit_decorated(node):
                out.add(node.name)
    return out


def _self_device_attrs(cls: ast.ClassDef, checker: "_Jaxiness") -> set[str]:
    """Attributes any method assigns a jax-bound value (`self.x = jnp...`)."""
    found: set[str] = set()
    # two passes: `self.x = jnp.array(self.x0)` may precede `self.x0 = ...`
    for _ in range(2):
        for node in ast.walk(cls):
            if not isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                continue
            value = node.value
            if value is None:
                continue
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            for t in targets:
                elts = t.elts if isinstance(t, ast.Tuple) else [t]
                for e in elts:
                    if (isinstance(e, ast.Attribute)
                            and isinstance(e.value, ast.Name)
                            and e.value.id == "self"
                            and checker.is_jaxy(value, set(), found)):
                        found.add(e.attr)
    return found


class _Jaxiness:
    """Decides whether an expression is jax-bound in a given scope."""

    def __init__(self, jax_aliases: set[str], np_aliases: set[str],
                 jit_funcs: set[str]):
        self.jax_aliases = jax_aliases
        self.np_aliases = np_aliases
        self.jit_funcs = jit_funcs

    def _chain_root(self, chain: Optional[str]) -> Optional[str]:
        return chain.split(".")[0] if chain else None

    def is_device_get(self, node: ast.Call) -> bool:
        chain = attr_chain(node.func)
        return bool(chain) and chain.split(".")[-1] == "device_get" \
            and self._chain_root(chain) in self.jax_aliases

    def is_np_call(self, node: ast.Call) -> bool:
        return self._chain_root(attr_chain(node.func)) in self.np_aliases

    def is_jaxy(self, node: ast.AST, names: set[str],
                self_attrs: set[str]) -> bool:
        j = lambda n: self.is_jaxy(n, names, self_attrs)  # noqa: E731
        if isinstance(node, ast.Name):
            return node.id in names
        if isinstance(node, ast.Attribute):
            if node.attr in METADATA_ATTRS:
                return False
            if (isinstance(node.value, ast.Name) and node.value.id == "self"
                    and node.attr in self_attrs):
                return True
            if node.attr in DEVICE_ATTRS:
                return True
            return j(node.value)
        if isinstance(node, ast.Subscript):
            return j(node.value)
        if isinstance(node, ast.Call):
            chain = attr_chain(node.func)
            root = self._chain_root(chain)
            if root in self.jax_aliases:
                return chain.split(".")[-1] not in JAX_HOST_FUNCS
            if isinstance(node.func, ast.Name):
                if node.func.id in self.jit_funcs:
                    return True
                if node.func.id in ("tuple", "list") and node.args:
                    return any(j(a) for a in node.args)
                return False
            if isinstance(node.func, ast.Attribute):
                if node.func.attr in self.jit_funcs:
                    return True  # module-qualified call, e.g. harness.<jit fn>
                # method of a jax value (x.reshape, x.at[...].set, ...)
                if node.func.attr in METADATA_ATTRS:
                    return False
                return j(node.func.value)
            return False
        if isinstance(node, (ast.BinOp,)):
            return j(node.left) or j(node.right)
        if isinstance(node, ast.UnaryOp):
            return j(node.operand)
        if isinstance(node, ast.Compare):
            return j(node.left) or any(j(c) for c in node.comparators)
        if isinstance(node, ast.BoolOp):
            return any(j(v) for v in node.values)
        if isinstance(node, ast.IfExp):
            return j(node.body) or j(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(j(e) for e in node.elts)
        if isinstance(node, (ast.GeneratorExp, ast.ListComp, ast.SetComp)):
            return j(node.elt)
        if isinstance(node, ast.Starred):
            return j(node.value)
        return False


class _FunctionScanner:
    """Scan one function body: infer jax-bound names, then flag sinks."""

    def __init__(self, jx: _Jaxiness, self_attrs: set[str], path: str):
        self.jx = jx
        self.self_attrs = self_attrs
        self.path = path
        self.names: set[str] = set()
        self.findings: list[Finding] = []

    def _jaxy(self, node: ast.AST) -> bool:
        return self.jx.is_jaxy(node, self.names, self.self_attrs)

    def _infer(self, body: list[ast.stmt]) -> None:
        # two passes: flow-insensitive fixpoint over assignment order
        for _ in range(2):
            for node in body:
                for sub in ast.walk(node):
                    if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        continue
                    if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                        if sub.value is None:
                            continue
                        targets = (sub.targets if isinstance(sub, ast.Assign)
                                   else [sub.target])
                        jaxy = self._jaxy(sub.value)
                        for t in targets:
                            elts = t.elts if isinstance(t, ast.Tuple) else [t]
                            for e in elts:
                                e = e.value if isinstance(e, ast.Starred) else e
                                if isinstance(e, ast.Name) and jaxy:
                                    self.names.add(e.id)
                    elif isinstance(sub, ast.AugAssign):
                        if (isinstance(sub.target, ast.Name)
                                and self._jaxy(sub.value)):
                            self.names.add(sub.target.id)
                    elif isinstance(sub, ast.For):
                        if self._jaxy(sub.iter):
                            for e in ast.walk(sub.target):
                                if isinstance(e, ast.Name):
                                    self.names.add(e.id)

    def _flag(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(Finding(
            CHECKER, rule, self.path, getattr(node, "lineno", 0), message,
            end_line=getattr(node, "end_lineno", 0) or 0,
        ))

    def _scan_sinks(self, body: list[ast.stmt]) -> None:
        for node in body:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    self._scan_call(sub)
                elif isinstance(sub, (ast.If, ast.While)):
                    if self._jaxy(sub.test):
                        self._flag(
                            "HS004", sub.test,
                            "truthiness test on a jax value blocks on device "
                            "completion; compute the predicate on host state "
                            "or keep the branch traced",
                        )
                elif isinstance(sub, ast.Assert):
                    if self._jaxy(sub.test):
                        self._flag(
                            "HS004", sub.test,
                            "assert on a jax value is a hidden device sync",
                        )

    def _scan_call(self, node: ast.Call) -> None:
        jx = self.jx
        if isinstance(node.func, ast.Name) and node.func.id in (
                "float", "int", "bool"):
            if any(self._jaxy(a) for a in node.args):
                self._flag(
                    "HS001", node,
                    f"{node.func.id}() on a jax value forces an implicit "
                    f"device->host sync; read it out with jax.device_get "
                    f"(+ pragma) or keep it on device",
                )
            return
        if (isinstance(node.func, ast.Attribute) and node.func.attr == "item"
                and not node.args and self._jaxy(node.func.value)):
            self._flag(
                "HS002", node,
                ".item() on a jax value is an implicit device->host sync",
            )
            return
        if jx.is_device_get(node):
            self._flag(
                "HS005", node,
                "jax.device_get is the audited explicit sync — annotate the "
                "line with `# repro: allow-host-sync(reason)`",
            )
            return
        if jx.is_np_call(node):
            args = list(node.args) + [k.value for k in node.keywords]
            if any(self._jaxy(a) for a in args):
                self._flag(
                    "HS003", node,
                    "np.* on a jax value copies device memory to host; use "
                    "jnp on device or jax.device_get (+ pragma) to read out",
                )


def check_source(source: str, path: str,
                 jit_funcs: Optional[set[str]] = None) -> list[Finding]:
    """Scan one module's source; returns pragma-filtered findings."""
    tree = ast.parse(source)
    jax_aliases, np_aliases = _module_aliases(tree)
    jx = _Jaxiness(jax_aliases, np_aliases,
                   jit_funcs or collect_jit_functions([tree]))
    findings: list[Finding] = []

    def scan_function(fn, self_attrs: set[str]) -> None:
        scanner = _FunctionScanner(jx, self_attrs, path)
        scanner._infer(fn.body)
        scanner._scan_sinks(fn.body)
        findings.extend(scanner.findings)

    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_function(node, set())
        elif isinstance(node, ast.ClassDef):
            self_attrs = _self_device_attrs(node, jx)
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    scan_function(item, self_attrs)
    return apply_pragmas(findings, parse_pragmas(source), path)


def check_paths(paths: list[str], root: str) -> list[Finding]:
    sources = {}
    for p in paths:
        with open(p, encoding="utf-8") as fh:
            sources[p] = fh.read()
    # global jit-function prescan: device-ness crosses module boundaries
    jit_funcs = collect_jit_functions(ast.parse(s) for s in sources.values())
    findings: list[Finding] = []
    for p, src in sources.items():
        findings.extend(
            check_source(src, os.path.relpath(p, root), jit_funcs=jit_funcs)
        )
    return findings


def run(root: str) -> list[Finding]:
    paths: list[str] = []
    for pattern in HOT_PATH_GLOBS:
        paths.extend(sorted(glob.glob(os.path.join(root, pattern))))
    paths = [p for p in paths if not p.endswith("__init__.py")]
    return check_paths(paths, root)
