"""CI hygiene gate: fail when generated artifacts are tracked in git.

Usage:
    python benchmarks/check_hygiene.py

These classes of generated files must never be committed:

* compiled Python bytecode (``*.pyc`` / ``__pycache__`` directories);
* benchmark outputs under ``artifacts/`` (``BENCH_*.json`` land there on
  every run — the COMMITTED copies live in ``benchmarks/baselines/``,
  which this gate deliberately does not match);
* Chrome-tracing timelines (``*.trace.json`` anywhere — serve runs emit
  them next to the bench JSON and they are upload-artifact material, not
  repo material);
* the JAX persistent compilation cache (``.jax_cache/``, written by the
  entry points through ``repro.compile_cache``) and hypothesis's example
  database (``.hypothesis/``, written by the property tests).

Violations print one ``::error file=...`` annotation per path so the CI
run summary links straight to the offending file.

The gate also requires ``.gitignore`` to cover every class it polices
(``REQUIRED_IGNORES``): tracked-file checks only catch an artifact
AFTER someone commits it — the ignore line is what stops ``git add -A``
from committing it in the first place.  The serve traces sat tracked
for three releases precisely because ``.gitignore`` had no
``*.trace.json`` line while this gate only matched ``BENCH_*``.

Stdlib-only on purpose — runs in the hygiene job before (and regardless
of) any jax install.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys

#: (label, pattern) pairs; a path matching ANY pattern is a violation.
RULES: tuple[tuple[str, re.Pattern], ...] = (
    ("compiled Python bytecode",
     re.compile(r"(^|/)__pycache__(/|$)|\.pyc$")),
    ("benchmark artifact JSON",
     re.compile(r"^artifacts/.*\.json$")),
    ("Chrome-tracing timeline",
     re.compile(r"\.trace\.json$")),
    ("JAX compilation cache",
     re.compile(r"^\.jax_cache/")),
    ("hypothesis example database",
     re.compile(r"^\.hypothesis/")),
)

#: Every artifact class RULES polices must also be git-ignored, so the
#: artifacts cannot be committed by a bulk ``git add`` in the first
#: place.  Exact-line match against .gitignore.
REQUIRED_IGNORES: tuple[str, ...] = (
    "__pycache__/",
    "*.pyc",
    "artifacts/BENCH_*.json",
    "artifacts/STATIC_*.json",
    "*.trace.json",
    ".jax_cache/",
    ".hypothesis/",
)


def find_violations(paths: list[str]) -> list[tuple[str, str]]:
    """Return ``(path, label)`` for every path matching a hygiene rule."""
    bad = []
    for p in paths:
        for label, rx in RULES:
            if rx.search(p):
                bad.append((p, label))
                break
    return bad


def gitignore_gaps(gitignore_lines: list[str]) -> list[str]:
    """The REQUIRED_IGNORES entries missing from the given .gitignore
    content (comments/blank lines ignored)."""
    present = {line.strip() for line in gitignore_lines
               if line.strip() and not line.strip().startswith("#")}
    return [pat for pat in REQUIRED_IGNORES if pat not in present]


def tracked_files() -> list[str]:
    """Every path git tracks, from the repo the cwd sits in."""
    res = subprocess.run(["git", "ls-files"], check=True,
                         capture_output=True, text=True)
    return [line for line in res.stdout.splitlines() if line]


def main() -> int:
    paths = tracked_files()
    bad = find_violations(paths)
    for path, label in bad:
        print(f"::error file={path}::{label} is tracked in git: {path}")
    gaps = (gitignore_gaps(open(".gitignore").read().splitlines())
            if os.path.exists(".gitignore") else list(REQUIRED_IGNORES))
    for pat in gaps:
        print(f"::error file=.gitignore::missing ignore pattern: {pat}")
    if bad or gaps:
        print(f"hygiene gate FAILED: {len(bad)} tracked artifact(s), "
              f"{len(gaps)} missing .gitignore pattern(s)")
        return 1
    print(f"hygiene gate passed ({len(paths)} tracked files clean, "
          f"{len(REQUIRED_IGNORES)} ignore patterns present)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
