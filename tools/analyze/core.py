"""Shared plumbing for the drift linters: findings, sources, allowlists.

Design constraints (docs/static-analysis.md):

- stdlib-``ast`` only, zero third-party deps — the suite must run in
  any container the tests run in;
- < 10 s on the 2-core CI box: every checker works off ONE shared
  parse of the tree (:class:`SourceSet` caches the ASTs);
- every intentional exception is EXPLICIT: each checker has an
  allowlist file under ``tools/analyze/allowlists/<checker>.txt`` whose
  entries must carry a reason AND match a live finding — an unexplained
  or unused (stale) entry is itself a finding, so the allowlists cannot
  silently rot into blanket mutes.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

ALLOWLIST_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "allowlists")


@dataclass(frozen=True)
class Finding:
    """One checker hit.

    ``key`` is the STABLE identity the allowlist matches on — never a
    line number (line-keyed suppressions rot on every unrelated edit).
    Each checker documents its key shape in docs/static-analysis.md.
    """

    checker: str
    file: str          # repo-root-relative path
    line: int
    key: str
    message: str

    def render(self) -> str:
        return (f"finding [{self.checker}] {self.file}:{self.line}: "
                f"{self.message}  (allowlist key: {self.file}:{self.key})")


@dataclass
class Allowlist:
    """Parsed ``<file>:<key>  <reason>`` entries for one checker."""

    checker: str
    entries: Dict[Tuple[str, str], str] = field(default_factory=dict)
    unexplained: List[Tuple[str, str]] = field(default_factory=list)
    used: set = field(default_factory=set)

    @classmethod
    def load(cls, checker: str,
             path: Optional[str] = None) -> "Allowlist":
        path = path or os.path.join(ALLOWLIST_DIR, f"{checker}.txt")
        al = cls(checker)
        if not os.path.exists(path):
            return al
        with open(path) as f:
            for raw in f:
                line = raw.strip()
                if not line or line.startswith("#"):
                    continue
                locator, _sep, reason = line.partition("  ")
                file, _sep2, key = locator.partition(":")
                entry = (file.strip(), key.strip())
                al.entries[entry] = reason.strip()
                if not reason.strip():
                    al.unexplained.append(entry)
        return al

    def filter(self, findings: Iterable[Finding]) -> List[Finding]:
        """Drop allowlisted findings; record which entries fired."""
        out = []
        for f in findings:
            entry = (f.file, f.key)
            if entry in self.entries:
                self.used.add(entry)
            else:
                out.append(f)
        return out

    def hygiene_findings(self) -> List[Finding]:
        """Unexplained or stale entries are findings of their own."""
        out = []
        for entry in self.unexplained:
            out.append(Finding(
                self.checker, entry[0], 0, entry[1],
                f"allowlist entry {entry[0]}:{entry[1]} has no reason "
                f"text — every exception must say why it is safe"))
        for entry, _reason in self.entries.items():
            if entry not in self.used and entry not in self.unexplained:
                out.append(Finding(
                    self.checker, entry[0], 0, entry[1],
                    f"stale allowlist entry {entry[0]}:{entry[1]} "
                    f"matches no current finding — delete it"))
        return out


class SourceSet:
    """The repo's python sources, parsed once and shared by checkers."""

    def __init__(self, root: str, rel_paths: List[str]):
        self.root = root
        self.trees: Dict[str, ast.Module] = {}
        self.texts: Dict[str, str] = {}
        self.parse_errors: List[Tuple[str, str]] = []
        for rel in rel_paths:
            full = os.path.join(root, rel)
            try:
                text = open(full, encoding="utf-8").read()
                self.trees[rel] = ast.parse(text, filename=rel)
                self.texts[rel] = text
            except (OSError, SyntaxError) as e:
                # a file that does not parse cannot be linted — surface
                # it as a finding rather than crashing the suite
                self.trees[rel] = ast.Module(body=[], type_ignores=[])
                self.texts[rel] = ""
                self.parse_errors.append((rel, str(e)))

    def items(self):
        return self.trees.items()


def discover_sources(root: str) -> List[str]:
    """Repo-relative python files the suite lints: the library and the
    two entry scripts (tests and tools lint themselves via their own
    suites)."""
    out: List[str] = []
    lib = os.path.join(root, "lightgbm_tpu")
    for dirpath, _dirs, files in os.walk(lib):
        for fn in sorted(files):
            if fn.endswith(".py"):
                out.append(os.path.relpath(os.path.join(dirpath, fn),
                                           root))
    for extra in ("__graft_entry__.py", "chip_smoke.py"):
        if os.path.exists(os.path.join(root, extra)):
            out.append(extra)
    return out


def attr_chain(node: ast.AST) -> str:
    """Dotted name of an attribute chain (``jax.lax.psum`` ->
    "jax.lax.psum"); "" when the node is not a plain chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def call_name(call: ast.Call) -> str:
    """Trailing name of a call target: ``obs.inc(...)`` -> "inc",
    ``psum(...)`` -> "psum"."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None
