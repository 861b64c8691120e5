"""Checker 3: Pallas kernel VMEM budgets and scalar placement.

Anghel et al. (PAPERS.md, arXiv:1809.04559) show GBDT kernels silently
lose correctness-per-watt in resource budgets, and this repo has exactly
one hand-enforced budget: the fused level-build program must fit
``FUSED_VMEM_BUDGET`` (12 MiB of the ~16 MiB/core VMEM; DESIGN.md §13) or
the learner falls back to the staged pipeline. Three machine checks:

  blockspec-scalar — AST scan of the kernel modules' ``pl.pallas_call``
      sites: a ``(1, 1)``-shaped (or all-ones) ``BlockSpec`` without
      ``memory_space=pltpu.SMEM`` parks a scalar in a full vector tile
      (the pre-PR-6 ``split_scan`` bug), and ``pl.ANY`` placement leaves
      the choice to the compiler. Scalars ride in SMEM, full stop.
  tuning-over-budget — every committed ``tuning_table.json`` row is
      re-priced through the real ``fused_level_vmem_bytes`` model at its
      own winning blocks: a row whose blocks exceed the budget describes
      a program the learner will never run (dispatch falls back), so it
      is either dead weight or a model/tuner disagreement.
  model-drift — ``fused_level_fits`` must agree with pricing the looked-up
      blocks directly; disagreement means the fits() fast path and the
      byte model diverged (someone edited one and not the other).

The schema validation from ``benchmarks/check_tuning_table`` (now a shim)
runs first — a malformed table fails here before anything prices it.
"""
from __future__ import annotations

import ast
import json
import pathlib

from repro.analysis import tuning_schema
from repro.analysis.findings import Finding

CHECKER = "vmem"

KERNEL_FILES = (
    "src/repro/kernels/histogram.py",
    "src/repro/kernels/histogram_sparse.py",
    "src/repro/kernels/split_scan.py",
    "src/repro/kernels/forest_traversal.py",
    "src/repro/kernels/level_build.py",
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


# ----------------------------------------------------- tuning-table pricing
def check_tuning_table(table_path: pathlib.Path, relpath: str) -> list[Finding]:
    try:
        table = json.loads(table_path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [
            Finding(
                CHECKER, "table-unreadable", "error", relpath, 0,
                f"cannot read tuning table: {e}", ident="table",
            )
        ]
    findings = [
        Finding(CHECKER, "table-schema", "error", relpath, 0, err, ident=err[:60])
        for err in tuning_schema.validate(table)
    ]
    if findings:
        return findings  # pricing a malformed table is meaningless
    try:
        from repro.kernels.level_build import (
            FUSED_VMEM_BUDGET,
            fused_level_fits,
            fused_level_vmem_bytes,
        )
    except ImportError:
        # stdlib-only environment (the lint-tier shim): schema checked,
        # budget pricing needs the jax stack — skip, the analysis CI job
        # runs the full check.
        return findings
    from repro.kernels import autotune

    for key, entry in table.get("entries", {}).items():
        n, f, b, l = tuning_schema.parse_geometry(key)
        f_pad, _ = autotune.feature_tiling(f, b, entry["feature_block"])
        nbytes = fused_level_vmem_bytes(
            l, l, f_pad, b, entry["sample_block"], entry["feature_block"]
        )
        if nbytes > FUSED_VMEM_BUDGET:
            findings.append(
                Finding(
                    CHECKER, "tuning-over-budget", "warning", relpath, 0,
                    f"{key}: tuned blocks (sb={entry['sample_block']}, "
                    f"fb={entry['feature_block']}) price at "
                    f"{nbytes / 2**20:.1f} MiB > the "
                    f"{FUSED_VMEM_BUDGET / 2**20:.0f} MiB fused budget — "
                    "the learner's fused_level_fits() falls back to the "
                    "staged pipeline at this geometry, so this row only "
                    "serves direct ops.level_build callers (kernel_bench)",
                    ident=key,
                )
            )
        # fits() must agree with pricing its own looked-up blocks: the
        # fast path and the byte model drifting apart means dispatch
        # decisions stop matching the documented budget math.
        blocks = autotune.lookup(n, f, b, l)
        f_pad, _ = autotune.feature_tiling(f, b, blocks["feature_block"])
        direct = (
            fused_level_vmem_bytes(
                l, l, f_pad, b, blocks["sample_block"], blocks["feature_block"]
            )
            <= FUSED_VMEM_BUDGET
        )
        if fused_level_fits(n, l, l, f, b) != direct:
            findings.append(
                Finding(
                    CHECKER, "model-drift", "error", relpath, 0,
                    f"{key}: fused_level_fits() disagrees with pricing the "
                    "looked-up blocks through fused_level_vmem_bytes() — "
                    "the VMEM model and the dispatch fast path have "
                    "diverged",
                    ident=f"drift:{key}",
                )
            )
    return findings


def check_repo(root: pathlib.Path) -> list[Finding]:
    findings: list[Finding] = []
    for rel in KERNEL_FILES:
        p = root / rel
        if p.exists():
            findings.extend(check_blockspecs(p, rel))
    table_rel = "src/repro/kernels/tuning_table.json"
    table = root / table_rel
    if table.exists():
        findings.extend(check_tuning_table(table, table_rel))
    else:
        findings.append(
            Finding(
                CHECKER, "table-missing", "error", table_rel, 0,
                "tuning_table.json is gone — dispatch silently falls back "
                "to DEFAULT_BLOCKS everywhere",
                ident="table",
            )
        )
    return findings
