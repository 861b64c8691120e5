"""Schema validation for ``src/repro/kernels/tuning_table.json``.

The tuning table is data the kernel dispatcher trusts at import time: a
malformed entry (a typo'd key, a string where a block size should be, a
format bump nobody taught the loader about) turns into a confusing
runtime failure deep inside a Pallas grid computation. This module is
stdlib-only — no jax import — so it runs in the lint tier; the VMEM
checker (``repro.analysis.vmem``) layers the budget cross-check on top.

Moved here from ``benchmarks/check_tuning_table.py`` (now a thin shim) so
the schema and the budget check share one entry point:
``python -m repro.analysis --only vmem``.
"""
from __future__ import annotations

import pathlib
import re

KEY_RE = re.compile(r"^N\d+_F\d+_B\d+_L\d+$")
KNOWN_FORMATS = {1}
# field -> (type, must be > 0)
ENTRY_FIELDS = {
    "sample_block": (int, True),
    "feature_block": (int, True),
    "fused_ms": (float, True),
    "split_ms": (float, True),
    "host": (str, False),
}


def default_table_path() -> pathlib.Path:
    return pathlib.Path(__file__).resolve().parents[1] / "kernels" / "tuning_table.json"


def parse_geometry(key: str) -> tuple[int, int, int, int]:
    """(N, F, B, L) from a ``N<d>_F<d>_B<d>_L<d>`` entry key."""
    parts = dict((seg[0], int(seg[1:])) for seg in key.split("_"))
    return parts["N"], parts["F"], parts["B"], parts["L"]


def validate(table: dict) -> list[str]:
    errors: list[str] = []
    fmt = table.get("format")
    if fmt not in KNOWN_FORMATS:
        errors.append(
            f"format is {fmt!r}; this validator knows {sorted(KNOWN_FORMATS)}"
            " — teach repro.analysis.tuning_schema (and the kernel loader)"
            " the new format before committing it"
        )
        return errors
    unknown_top = set(table) - {"format", "entries", "comment"}
    if unknown_top:
        errors.append(f"unknown top-level fields: {sorted(unknown_top)}")
    entries = table.get("entries")
    if not isinstance(entries, dict):
        errors.append("'entries' must be an object")
        return errors
    for key, entry in entries.items():
        if not KEY_RE.match(key):
            errors.append(f"entry key {key!r} does not match N<d>_F<d>_B<d>_L<d>")
        if not isinstance(entry, dict):
            errors.append(f"{key}: entry must be an object")
            continue
        for field, (typ, positive) in ENTRY_FIELDS.items():
            val = entry.get(field)
            if val is None:
                errors.append(f"{key}: missing field {field!r}")
            elif typ is float:
                if isinstance(val, bool) or not isinstance(val, (int, float)):
                    errors.append(f"{key}.{field}: {val!r} is not a number")
                elif positive and val <= 0:
                    errors.append(f"{key}.{field}: must be > 0, got {val}")
            elif typ is int:
                if isinstance(val, bool) or not isinstance(val, int):
                    errors.append(f"{key}.{field}: {val!r} is not an int")
                elif positive and val <= 0:
                    errors.append(f"{key}.{field}: must be > 0, got {val}")
            elif not isinstance(val, typ):
                errors.append(f"{key}.{field}: {val!r} is not {typ.__name__}")
        unknown = set(entry) - set(ENTRY_FIELDS)
        if unknown:
            errors.append(f"{key}: unknown fields {sorted(unknown)}")
    return errors
