"""Pallas resource/shape checker (rules PL001-PL005).

A Pallas kernel's resource story is written in three places that nothing
ties together at runtime until a TPU OOMs or Mosaic rejects the lowering:
the BlockSpecs/scratch_shapes (what lives in VMEM/SMEM), the grid (how many
index-map arguments each lambda must take), and ``input_output_aliases``
(which HBM buffers are donated). This checker parses each kernel wrapper in
`repro.kernels`, statically evaluates every shape expression at the
representative points declared in `repro.kernels.budgets.KERNEL_BUDGETS`,
and enforces:

PL001  VMEM/SMEM footprint exceeds the kernel's declared budget at a point
PL002  a pallas_call with no budget entry, or a budget entry whose kernel
       no longer exists (dead contract)
PL003  rank mismatches: index-map arity vs grid (+ scalar-prefetch) rank,
       index-map result rank vs block rank, out_specs vs out_shape arity
PL004  aliasing/donation hazards: an ``input_output_aliases`` index out of
       range, or an alias whose input/output operand is a *pipelined*
       (windowed) BlockSpec — aliasing is only sound for manually-DMA'd
       ``memory_space=ANY`` operands, where the kernel controls write order
PL005  a shape expression the checker cannot resolve at a budget point
       (the budget's point dict is missing a dimension name)

Footprint model: 4 bytes/element everywhere (all kernel operands are
f32/int32), windowed specs double-buffered, ``ANY`` operands free (HBM),
declared ``temp_bytes`` added per point — see `repro.kernels.budgets`.
"""
from __future__ import annotations

import ast
import dataclasses
import glob
import math
import os
from typing import Optional

from tools.check.common import Finding, ShapeEvalError, attr_chain, eval_shape_expr

CHECKER = "pallas"
BYTES_PER_ELEM = 4


@dataclasses.dataclass
class Spec:
    """One BlockSpec: a window (shape + index map) or a memory-space pin."""

    shape: Optional[ast.AST]        # block-shape expression, None if absent
    index_map: Optional[ast.Lambda]
    memory_space: Optional[str]     # "ANY" | "VMEM" | "SMEM" | None
    line: int

    @property
    def windowed(self) -> bool:
        return self.shape is not None


@dataclasses.dataclass
class Scratch:
    kind: str                       # "VMEM" | "SMEM" | "sem"
    shape: Optional[ast.AST]
    line: int


@dataclasses.dataclass
class KernelSite:
    """One pl.pallas_call + its grid spec, as parsed from source."""

    name: str                       # enclosing wrapper function name
    path: str
    line: int
    grid: Optional[ast.AST] = None
    num_scalar_prefetch: int = 0
    in_specs: list = dataclasses.field(default_factory=list)
    out_specs: list = dataclasses.field(default_factory=list)
    scratch: list = dataclasses.field(default_factory=list)
    out_shapes: list = dataclasses.field(default_factory=list)  # shape exprs
    aliases: dict = dataclasses.field(default_factory=dict)


def _chain_ends(node: ast.AST, suffix: str) -> bool:
    chain = attr_chain(node)
    return bool(chain) and chain.split(".")[-1] == suffix


def _parse_blockspec(node: ast.AST) -> Optional[Spec]:
    if not (isinstance(node, ast.Call) and _chain_ends(node.func, "BlockSpec")):
        return None
    shape = index_map = None
    memory_space = None
    if node.args:
        shape = node.args[0]
        if len(node.args) > 1 and isinstance(node.args[1], ast.Lambda):
            index_map = node.args[1]
    for kw in node.keywords:
        if kw.arg == "index_map" and isinstance(kw.value, ast.Lambda):
            index_map = kw.value
        elif kw.arg == "block_shape":
            shape = kw.value
        elif kw.arg == "memory_space":
            chain = attr_chain(kw.value) or ""
            memory_space = chain.split(".")[-1] or None
    return Spec(shape, index_map, memory_space, node.lineno)


def _parse_scratch(node: ast.AST) -> Optional[Scratch]:
    if isinstance(node, ast.Call):
        chain = attr_chain(node.func) or ""
        leaf = chain.split(".")[-1]
        if leaf in ("VMEM", "SMEM"):
            return Scratch(leaf, node.args[0] if node.args else None,
                           node.lineno)
        if leaf == "DMA":
            return Scratch("sem", None, node.lineno)
    elif isinstance(node, ast.Attribute) and _chain_ends(node, "DMA"):
        return Scratch("sem", None, node.lineno)
    return None


def _spec_list(node: ast.AST) -> list:
    elts = node.elts if isinstance(node, (ast.List, ast.Tuple)) else [node]
    return [_parse_blockspec(e) or e for e in elts]


def _parse_out_shapes(node: ast.AST) -> list:
    elts = node.elts if isinstance(node, (ast.List, ast.Tuple)) else [node]
    shapes = []
    for e in elts:
        if (isinstance(e, ast.Call)
                and _chain_ends(e.func, "ShapeDtypeStruct") and e.args):
            shapes.append(e.args[0])
        else:
            shapes.append(None)
    return shapes


def _extract_sites(tree: ast.Module, path: str) -> list[KernelSite]:
    sites: list[KernelSite] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        grid_specs: dict[str, ast.Call] = {}   # name -> PrefetchScalarGridSpec
        calls: list[ast.Call] = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if _chain_ends(node.func, "PrefetchScalarGridSpec"):
                grid_specs["<inline>"] = node
            elif _chain_ends(node.func, "pallas_call"):
                calls.append(node)
        for node in ast.walk(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)
                    and _chain_ends(node.value.func, "PrefetchScalarGridSpec")):
                grid_specs[node.targets[0].id] = node.value
        for call in calls:
            site = KernelSite(fn.name, path, call.lineno)
            gs: Optional[ast.Call] = None
            for kw in call.keywords:
                if kw.arg == "grid_spec":
                    if isinstance(kw.value, ast.Name):
                        gs = grid_specs.get(kw.value.id)
                    elif isinstance(kw.value, ast.Call):
                        gs = kw.value
                elif kw.arg == "out_shape":
                    site.out_shapes = _parse_out_shapes(kw.value)
                elif kw.arg == "input_output_aliases":
                    if isinstance(kw.value, ast.Dict):
                        for k, v in zip(kw.value.keys, kw.value.values, strict=True):
                            if (isinstance(k, ast.Constant)
                                    and isinstance(v, ast.Constant)):
                                site.aliases[k.value] = v.value
                elif kw.arg in ("grid", "in_specs", "out_specs",
                                "scratch_shapes"):
                    gs_kw = kw  # plain pallas_call spelling (fixtures)
                    if kw.arg == "grid":
                        site.grid = kw.value
                    elif kw.arg == "in_specs":
                        site.in_specs = _spec_list(kw.value)
                    elif kw.arg == "out_specs":
                        site.out_specs = _spec_list(kw.value)
                    else:
                        site.scratch = [
                            s for s in map(
                                _parse_scratch,
                                kw.value.elts
                                if isinstance(kw.value, (ast.List, ast.Tuple))
                                else [],
                            ) if s
                        ]
                    del gs_kw
            if gs is not None:
                for kw in gs.keywords:
                    if kw.arg == "grid":
                        site.grid = kw.value
                    elif kw.arg == "num_scalar_prefetch":
                        if isinstance(kw.value, ast.Constant):
                            site.num_scalar_prefetch = int(kw.value.value)
                    elif kw.arg == "in_specs":
                        site.in_specs = _spec_list(kw.value)
                    elif kw.arg == "out_specs":
                        site.out_specs = _spec_list(kw.value)
                    elif kw.arg == "scratch_shapes":
                        elts = (kw.value.elts
                                if isinstance(kw.value, (ast.List, ast.Tuple))
                                else [])
                        site.scratch = [
                            s for s in map(_parse_scratch, elts) if s
                        ]
            sites.append(site)
    return sites


def _bytes_of(shape_node: ast.AST, env: dict) -> int:
    shape = eval_shape_expr(shape_node, env)
    if not isinstance(shape, tuple):
        shape = (shape,)
    return int(math.prod(int(s) for s in shape)) * BYTES_PER_ELEM


def _lambda_arity(lam: ast.Lambda) -> tuple[int, bool]:
    a = lam.args
    return len(a.posonlyargs) + len(a.args), a.vararg is not None


def _check_rank(site: KernelSite, grid_rank: int, spec: Spec,
                which: str, findings: list[Finding]) -> None:
    if spec.index_map is None:
        return
    nargs, vararg = _lambda_arity(spec.index_map)
    want = grid_rank + site.num_scalar_prefetch
    if vararg:
        if nargs > want:
            findings.append(Finding(
                CHECKER, "PL003", site.path, spec.line,
                f"{site.name}: {which} index map takes {nargs} fixed args + "
                f"*rest but the grid supplies only {want} "
                f"(grid rank {grid_rank} + {site.num_scalar_prefetch} "
                f"prefetch refs)",
            ))
    elif nargs != want:
        findings.append(Finding(
            CHECKER, "PL003", site.path, spec.line,
            f"{site.name}: {which} index map takes {nargs} args, expected "
            f"{want} (grid rank {grid_rank} + {site.num_scalar_prefetch} "
            f"scalar-prefetch refs)",
        ))
    if spec.shape is not None:
        block_rank = (len(spec.shape.elts)
                      if isinstance(spec.shape, ast.Tuple) else 1)
        body = spec.index_map.body
        out_rank = len(body.elts) if isinstance(body, ast.Tuple) else 1
        if out_rank != block_rank:
            findings.append(Finding(
                CHECKER, "PL003", site.path, spec.line,
                f"{site.name}: {which} index map returns {out_rank} "
                f"coordinates for a rank-{block_rank} block",
            ))


def _check_aliases(site: KernelSite, findings: list[Finding]) -> None:
    n_in = site.num_scalar_prefetch + len(site.in_specs)
    n_out = max(len(site.out_specs), len(site.out_shapes))
    for k, v in site.aliases.items():
        if not (0 <= k < n_in) or not (0 <= v < n_out):
            findings.append(Finding(
                CHECKER, "PL004", site.path, site.line,
                f"{site.name}: input_output_aliases {{{k}: {v}}} out of "
                f"range for {n_in} inputs / {n_out} outputs (alias indices "
                f"count scalar-prefetch operands)",
            ))
            continue
        if k < site.num_scalar_prefetch:
            findings.append(Finding(
                CHECKER, "PL004", site.path, site.line,
                f"{site.name}: alias input {k} is a scalar-prefetch operand "
                f"— donating SMEM prefetch refs is never sound",
            ))
            continue
        for spec, which in ((site.in_specs[k - site.num_scalar_prefetch],
                             f"input {k}"),
                            (site.out_specs[v] if v < len(site.out_specs)
                             else None, f"output {v}")):
            if isinstance(spec, Spec) and (
                    spec.windowed or spec.memory_space == "VMEM"):
                findings.append(Finding(
                    CHECKER, "PL004", site.path, spec.line,
                    f"{site.name}: aliased {which} is a pipelined "
                    f"({spec.memory_space or 'windowed'}) operand; aliasing "
                    f"is only sound for memory_space=ANY buffers whose "
                    f"write order the kernel controls",
                ))


def _footprint_at(site: KernelSite, env: dict) -> tuple[int, int]:
    """(vmem_bytes, smem_bytes) at one point; raises ShapeEvalError."""
    vmem = smem = 0
    for s in site.scratch:
        if s.kind == "VMEM" and s.shape is not None:
            vmem += _bytes_of(s.shape, env)
        elif s.kind == "SMEM" and s.shape is not None:
            smem += _bytes_of(s.shape, env)
    for spec in site.in_specs:
        if isinstance(spec, Spec) and spec.windowed:
            vmem += 2 * _bytes_of(spec.shape, env)   # double-buffered window
    for i, spec in enumerate(site.out_specs):
        if not isinstance(spec, Spec):
            continue
        if spec.windowed:
            vmem += 2 * _bytes_of(spec.shape, env)
        elif spec.memory_space in ("VMEM", "SMEM") \
                and i < len(site.out_shapes) \
                and site.out_shapes[i] is not None:
            b = _bytes_of(site.out_shapes[i], env)  # whole-array output
            if spec.memory_space == "VMEM":
                vmem += b
            else:
                smem += b
    return vmem, smem


def check_sites(sites: list[KernelSite], budgets: dict) -> list[Finding]:
    findings: list[Finding] = []
    seen: set[str] = set()
    for site in sites:
        seen.add(site.name)
        budget = budgets.get(site.name)
        if budget is None:
            findings.append(Finding(
                CHECKER, "PL002", site.path, site.line,
                f"pallas_call in {site.name!r} has no "
                f"kernels.budgets.KERNEL_BUDGETS entry — every kernel "
                f"declares its VMEM/SMEM ceiling",
            ))
            continue
        grid_rank = (len(site.grid.elts)
                     if isinstance(site.grid, ast.Tuple) else 1)
        for spec in site.in_specs:
            if isinstance(spec, Spec):
                _check_rank(site, grid_rank, spec, "in_spec", findings)
        for spec in site.out_specs:
            if isinstance(spec, Spec):
                _check_rank(site, grid_rank, spec, "out_spec", findings)
        if site.out_shapes and site.out_specs \
                and len(site.out_shapes) != len(site.out_specs):
            findings.append(Finding(
                CHECKER, "PL003", site.path, site.line,
                f"{site.name}: {len(site.out_specs)} out_specs for "
                f"{len(site.out_shapes)} out_shape entries",
            ))
        _check_aliases(site, findings)
        for point in budget.points:
            env = dict(point)
            if "n" not in env and "nb" in env and "bs" in env:
                env["n"] = env["nb"] * env["bs"]
            try:
                vmem, smem = _footprint_at(site, env)
            except ShapeEvalError as e:
                findings.append(Finding(
                    CHECKER, "PL005", site.path, site.line,
                    f"{site.name}: unresolvable shape at point {point}: {e}",
                ))
                continue
            vmem += int(env.get("temp_bytes", 0))
            if vmem > budget.vmem_limit_bytes:
                findings.append(Finding(
                    CHECKER, "PL001", site.path, site.line,
                    f"{site.name}: VMEM footprint {vmem} B exceeds budget "
                    f"{budget.vmem_limit_bytes} B at point {point}",
                ))
            if smem > budget.smem_limit_bytes:
                findings.append(Finding(
                    CHECKER, "PL001", site.path, site.line,
                    f"{site.name}: SMEM footprint {smem} B exceeds budget "
                    f"{budget.smem_limit_bytes} B at point {point}",
                ))
    for name in sorted(set(budgets) - seen):
        findings.append(Finding(
            CHECKER, "PL002", "<budgets>", 0,
            f"KERNEL_BUDGETS entry {name!r} matches no pallas_call wrapper "
            f"in the scanned kernels (dead contract)",
        ))
    return findings


def collect_sites(paths: list[str], root: str) -> list[KernelSite]:
    sites: list[KernelSite] = []
    for p in paths:
        with open(p, encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        sites.extend(_extract_sites(tree, os.path.relpath(p, root)))
    return sites


def footprints(root: str) -> dict[str, list[tuple[dict, int, int]]]:
    """Per-kernel (point, vmem_bytes, smem_bytes) rows — README table input."""
    from repro.kernels.budgets import KERNEL_BUDGETS

    paths = sorted(glob.glob(os.path.join(root, "src/repro/kernels/*.py")))
    out: dict[str, list[tuple[dict, int, int]]] = {}
    for site in collect_sites(paths, root):
        budget = KERNEL_BUDGETS.get(site.name)
        if budget is None:
            continue
        rows = []
        for point in budget.points:
            env = dict(point)
            if "n" not in env and "nb" in env and "bs" in env:
                env["n"] = env["nb"] * env["bs"]
            vmem, smem = _footprint_at(site, env)
            rows.append((point, vmem + int(env.get("temp_bytes", 0)), smem))
        out[site.name] = rows
    return out


def run(root: str) -> list[Finding]:
    from repro.kernels.budgets import KERNEL_BUDGETS

    paths = sorted(glob.glob(os.path.join(root, "src/repro/kernels/*.py")))
    return check_sites(collect_sites(paths, root), KERNEL_BUDGETS)
