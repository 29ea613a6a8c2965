#!/usr/bin/env python
"""Lint gate: the shard_map/vma surface lives in ONE file.

``src/repro/compat.py`` is the only module that touches JAX's vma-typed
shard_map surface (``jax.shard_map`` / ``lax.pcast`` / ``lax.pvary`` /
``jax.typeof``); the rest of ``src/`` calls ``compat.shard_map`` /
``compat.pvary`` / ``compat.vma_of`` / ``compat.out_struct``, so a JAX
upgrade touches exactly one module.

This script fails (exit 1) when any file under ``src/`` other than
``compat.py`` uses the raw surface:

  * ``jax.shard_map`` / ``jax.experimental.shard_map``
  * ``lax.pvary`` / ``lax.pcast``
  * ``jax.typeof``
  * a ``check_rep=`` keyword (the old shard_map spelling)

Run locally:  python tools/check_compat.py
CI runs it as the blocking ``lint`` job.
"""
from __future__ import annotations

import pathlib
import re
import sys

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"

# (human label, regex) — matched per line, comments stripped first, so a
# docstring mention still trips; keep compat-surface discussion in compat.py
PATTERNS = (
    ("jax.shard_map", re.compile(r"\bjax\.shard_map\b")),
    ("jax.experimental.shard_map",
     re.compile(r"\bjax\.experimental\.shard_map\b|"
                r"from\s+jax\.experimental\.shard_map\s+import|"
                r"from\s+jax\.experimental\s+import\s+shard_map")),
    ("lax.pvary", re.compile(r"\blax\.pvary\b")),
    ("lax.pcast", re.compile(r"\blax\.pcast\b")),
    ("jax.typeof", re.compile(r"\bjax\.typeof\b")),
    ("check_rep=", re.compile(r"\bcheck_rep\s*=")),
)


def check_file(path: pathlib.Path) -> list[str]:
    errs = []
    in_doc = False
    for lineno, line in enumerate(path.read_text().splitlines(), 1):
        # strip # comments (good-enough lexing: no '#' inside the patterns)
        code = line.split("#", 1)[0]
        # skip docstring bodies: they legitimately *discuss* the raw API
        stripped = code.strip()
        quote_count = stripped.count('"""') + stripped.count("'''")
        if in_doc:
            if quote_count % 2:
                in_doc = False
            continue
        if quote_count % 2:
            in_doc = True
            code = code.split('"""')[0].split("'''")[0]
        for label, rx in PATTERNS:
            if rx.search(code):
                errs.append(f"{path.relative_to(SRC.parent)}:{lineno}: "
                            f"direct use of {label} — route through "
                            f"repro.compat")
    return errs


def main() -> int:
    errors: list[str] = []
    for path in sorted(SRC.rglob("*.py")):
        if path.name == "compat.py":
            continue
        errors.extend(check_file(path))
    if errors:
        print("\n".join(errors))
        print(f"\n{len(errors)} compat violation(s): only src/repro/compat.py "
              f"may touch the raw shard_map/pvary/pcast/typeof surface.")
        return 1
    print("compat check: OK (all raw shard_map/pvary/typeof uses are in "
          "compat.py)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
