"""Findings, suppressions, and the committed baseline.

Every checker in ``repro.analysis`` reports ``Finding`` records. A finding
is identified across runs by its FINGERPRINT — ``checker:code:file:ident``
— deliberately excluding the line number, so an unrelated edit that shifts
a justified finding down a few lines does not break CI. The committed
baseline (``analysis_baseline.json`` at the repo root) lists fingerprints
that are KNOWN and JUSTIFIED; the CLI exits non-zero only on findings
absent from it. The workflow mirrors every ratchet gate in this repo
(coverage floor, bench snapshot): new violations fail, grandfathered ones
are visible, and removing a stale baseline entry is a one-line diff.

Inline suppression: a ``# analysis: ignore[CODE]`` comment on the
offending line silences that code there — for the rare case where the
checker is right about the pattern but wrong about the instance; the
comment itself is the written-down justification.

This module is stdlib-only (no jax) so the lint tier can import it
through the package on a runner that never installed the ML stack.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import re

SEVERITIES = ("error", "warning")

_IGNORE_RE = re.compile(r"#\s*analysis:\s*ignore\[([A-Za-z0-9_\-, ]+)\]")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation of a machine-checked invariant.

    ``ident`` is the stable within-file identifier the fingerprint uses
    instead of the line number: a function/variable name, a primitive
    name — whatever survives unrelated edits.
    """

    checker: str  # 'determinism' | 'locks' | 'vmem' | 'lints'
    code: str  # short rule id, e.g. 'seam-crossing', 'unguarded-read'
    severity: str  # 'error' | 'warning'
    file: str  # repo-relative path (or '<traced>' for jaxpr audits)
    line: int  # 1-based; 0 when no source location applies
    message: str
    ident: str = ""

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"unknown severity {self.severity!r}")

    @property
    def fingerprint(self) -> str:
        ident = self.ident or f"L{self.line}"
        return f"{self.checker}:{self.code}:{self.file}:{ident}"

    def render(self) -> str:
        loc = f"{self.file}:{self.line}" if self.line else self.file
        return f"{self.severity:7s} {self.checker}:{self.code} {loc}  {self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self) | {"fingerprint": self.fingerprint}


def ignored_codes(source_line: str) -> set[str]:
    """Codes suppressed by a ``# analysis: ignore[...]`` comment, if any."""
    m = _IGNORE_RE.search(source_line)
    if not m:
        return set()
    return {c.strip() for c in m.group(1).split(",") if c.strip()}


def apply_suppressions(
    findings: list[Finding], sources: dict[str, list[str]]
) -> list[Finding]:
    """Drop findings whose source line carries an ignore pragma for their
    code. ``sources`` maps repo-relative path -> list of lines."""
    out = []
    for f in findings:
        lines = sources.get(f.file)
        if lines and 0 < f.line <= len(lines):
            codes = ignored_codes(lines[f.line - 1])
            if f.code in codes or "all" in codes:
                continue
        out.append(f)
    return out


# ------------------------------------------------------------------ baseline
def load_baseline(path: pathlib.Path) -> dict[str, str]:
    """{fingerprint: justification} from the committed baseline file.

    A missing file is an empty baseline (the clean-repo default); a
    malformed one raises — a baseline nobody can parse is a gate nobody
    can trust.
    """
    if not path.exists():
        return {}
    raw = json.loads(path.read_text())
    entries = raw.get("findings", [])
    out: dict[str, str] = {}
    for e in entries:
        fp = e.get("fingerprint")
        if not isinstance(fp, str) or not fp:
            raise ValueError(f"{path}: baseline entry without fingerprint: {e!r}")
        if not isinstance(e.get("justification"), str) or not e["justification"]:
            raise ValueError(
                f"{path}: baseline entry {fp!r} has no justification — "
                "every grandfathered finding must say WHY it is acceptable"
            )
        out[fp] = e["justification"]
    return out


def save_baseline(path: pathlib.Path, findings: list[Finding], justification: str) -> None:
    """Write the current findings as the new baseline (one shared
    placeholder justification — edit the file to write real ones)."""
    payload = {
        "comment": "accepted repro.analysis findings; regenerate with "
        "`PYTHONPATH=src python -m repro.analysis --write-baseline`, then "
        "edit each entry's justification",
        "findings": [
            {
                "fingerprint": f.fingerprint,
                "message": f.message,
                "justification": justification,
            }
            for f in sorted(findings, key=lambda f: f.fingerprint)
        ],
    }
    path.write_text(json.dumps(payload, indent=1) + "\n")


def split_by_baseline(
    findings: list[Finding], baseline: dict[str, str]
) -> tuple[list[Finding], list[Finding], list[str]]:
    """(new, baselined, stale_fingerprints).

    ``stale`` lists baseline entries no run produced — fixed violations
    whose baseline line should now be deleted (reported, never fatal).
    """
    seen = {f.fingerprint for f in findings}
    new = [f for f in findings if f.fingerprint not in baseline]
    old = [f for f in findings if f.fingerprint in baseline]
    stale = sorted(set(baseline) - seen)
    return new, old, stale
