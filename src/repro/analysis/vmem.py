"""Checker 3: Pallas BlockSpec placement.

AST scan of the kernel modules' ``pl.pallas_call`` sites:

  blockspec-scalar — a ``(1, 1)``-shaped (or all-ones) ``BlockSpec``
      without ``memory_space=pltpu.SMEM`` parks a scalar in a full vector
      tile (a placement ``split_scan`` once had);
  blockspec-any — ``pl.ANY`` placement leaves the choice to the compiler.

Scalars ride in SMEM, full stop.
"""
from __future__ import annotations

import ast
import pathlib

from repro.analysis.findings import Finding

CHECKER = "vmem"

KERNEL_FILES = (
    "src/repro/kernels/histogram.py",
    "src/repro/kernels/histogram_sparse.py",
    "src/repro/kernels/split_scan.py",
    "src/repro/kernels/forest_traversal.py",
)


# ----------------------------------------------------------- AST: BlockSpec
def _is_all_ones_tuple(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Tuple)
        and len(node.elts) >= 1
        and all(isinstance(e, ast.Constant) and e.value == 1 for e in node.elts)
    )


def _kw(call: ast.Call, name: str) -> ast.AST | None:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _spec_calls(tree: ast.Module):
    """Every ``BlockSpec(...)`` call node in the module."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else getattr(fn, "id", None)
            if name == "BlockSpec":
                yield node


def check_blockspecs(path: pathlib.Path, relpath: str) -> list[Finding]:
    tree = ast.parse(path.read_text(), filename=str(path))
    findings = []
    for call in _spec_calls(tree):
        mem = _kw(call, "memory_space")
        mem_name = ast.unparse(mem) if mem is not None else ""
        if "ANY" in mem_name:
            findings.append(
                Finding(
                    CHECKER, "blockspec-any", "error", relpath, call.lineno,
                    "BlockSpec(memory_space=ANY) leaves operand placement "
                    "to the compiler — pin scalars to SMEM and arrays to "
                    "the default VMEM pipeline explicitly",
                    ident=f"L{call.lineno}",
                )
            )
            continue
        shape = call.args[0] if call.args else None
        if shape is not None and _is_all_ones_tuple(shape) and "SMEM" not in mem_name:
            findings.append(
                Finding(
                    CHECKER, "blockspec-scalar", "error", relpath, call.lineno,
                    f"scalar operand BlockSpec({ast.unparse(shape)}) is not "
                    "placed in SMEM — a lone scalar in a vector tile burns "
                    "a VMEM window and serializes against the block DMA "
                    "pipeline (the pre-PR-6 split_scan placement)",
                    ident=f"L{call.lineno}",
                )
            )
    return findings


def check_repo(root: pathlib.Path) -> list[Finding]:
    findings: list[Finding] = []
    for rel in KERNEL_FILES:
        p = root / rel
        if p.exists():
            findings.extend(check_blockspecs(p, rel))
    return findings
