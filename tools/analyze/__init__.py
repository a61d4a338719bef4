"""Repo-native static analysis: six drift linters + allowlists.

``python -m tools.analyze`` — dependency-free (stdlib ``ast``), < 10 s,
held at zero findings by ``tests/test_analysis.py`` (tier-1). Catalogue,
allowlist workflow and how-to-add-a-checker: docs/static-analysis.md.

Checkers (each with ``tools/analyze/allowlists/<name>.txt``):

- ``capability-gate``      — eligibility literals outside capabilities.py
- ``config-knobs``         — raw/undeclared/undocumented ``tpu_*`` knobs
- ``obs-names``            — code ⟂ docs/observability.md catalogue drift
- ``collective-safety``    — collectives inside lax.switch/cond branches
                             or rank-divergent conditionals (PR 12 class)
- ``lock-discipline``      — obs shared state mutated outside the lock
- ``donation-discipline``  — a donated jit argument read again before
                             reassignment (use-after-donate, PR 16 class)
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Dict, List, Optional

from . import (capability_gate, collective_safety, config_knobs,
               donation_discipline, lock_discipline, obs_names)
from .core import Allowlist, Finding, SourceSet, discover_sources

CHECKERS = {
    capability_gate.NAME: capability_gate.check,
    config_knobs.NAME: config_knobs.check,
    obs_names.NAME: obs_names.check,
    collective_safety.NAME: collective_safety.check,
    lock_discipline.NAME: lock_discipline.check,
    donation_discipline.NAME: donation_discipline.check,
}

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def run(root: Optional[str] = None,
        checkers: Optional[List[str]] = None,
        use_allowlists: bool = True) -> List[Finding]:
    """All post-allowlist findings (plus allowlist-hygiene findings)."""
    root = root or REPO_ROOT
    sources = SourceSet(root, discover_sources(root))
    findings: List[Finding] = []
    for rel, err in sources.parse_errors:
        findings.append(Finding("parse", rel, 0, "syntax-error",
                                f"cannot parse: {err}"))
    for name in (checkers or sorted(CHECKERS)):
        raw = CHECKERS[name](sources)
        if use_allowlists:
            al = Allowlist.load(name)
            findings.extend(al.filter(raw))
            findings.extend(al.hygiene_findings())
        else:
            findings.extend(raw)
    return findings


def run_checker_on_source(name: str, source: str,
                          rel: str = "lightgbm_tpu/_fixture.py",
                          root: Optional[str] = None) -> List[Finding]:
    """Run ONE checker over an in-memory snippet (the fixture tests'
    entry point). The snippet is parsed under ``rel`` so path-scoped
    checkers (lock-discipline's obs/ scope) can be exercised; the real
    config.py rides along so config-knobs checks the snippet against
    the REAL declaration table; no allowlist is applied. Findings are
    returned for the snippet only."""
    import ast as _ast
    root = root or REPO_ROOT
    base = [config_knobs.CONFIG_FILE] if os.path.exists(
        os.path.join(root, config_knobs.CONFIG_FILE)) else []
    sources = SourceSet(root, base)
    sources.trees[rel] = _ast.parse(source)
    sources.texts[rel] = source
    return [f for f in CHECKERS[name](sources) if f.file == rel]


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.analyze",
        description="repo-native drift linters (docs/static-analysis.md)")
    ap.add_argument("--root", default=REPO_ROOT)
    ap.add_argument("--checker", action="append",
                    help="run only this checker (repeatable)")
    ap.add_argument("--no-allowlists", action="store_true",
                    help="show findings the allowlists would suppress")
    args = ap.parse_args(argv)
    for c in (args.checker or []):
        if c not in CHECKERS:
            ap.error(f"unknown checker {c!r} (known: "
                     f"{', '.join(sorted(CHECKERS))})")
    t0 = time.monotonic()
    findings = run(args.root, args.checker,
                   use_allowlists=not args.no_allowlists)
    for f in findings:
        print(f.render())
    n = len(findings)
    print(f"tools.analyze: {n} finding(s) across "
          f"{len(args.checker or CHECKERS)} checker(s) "
          f"in {time.monotonic() - t0:.2f}s")
    return 1 if n else 0
