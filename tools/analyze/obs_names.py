"""Checker 3: obs-name drift — code and docs/observability.md agree on
the metric/span/heartbeat name catalogue, in BOTH directions.

- **undocumented** — a name emitted in code (first constant-string arg
  of ``obs.inc`` / ``obs.observe`` / ``obs.set_gauge`` / ``obs.span`` /
  ``registry().counter|gauge|histogram``) that the catalogue does not
  list: dashboards cannot discover it.
- **unemitted** — a catalogued name no code emits: the doc describes a
  signal that does not exist (the rot direction PR 13's review caught
  by hand).

Docs side: backticked tokens in docs/observability.md shaped like a
metric name (lowercase dotted/slashed path). ``slo.*``-style entries
are prefix wildcards. ``{label=...}`` suffixes are stripped. Tokens
that are obviously API/file references (``obs.enable``, ``*.py``) are
ignored. Code side: names built dynamically (f-strings, dict-driven
gauges) are invisible to the AST — catalogue entries for those go in
the allowlist with the reason naming the emitting site.

Keys: ``undocumented:<name>``, ``unemitted:<name>``.
"""
from __future__ import annotations

import ast
import os
import re
from typing import List, Set, Tuple

from .core import Finding, SourceSet, call_name, const_str

NAME = "obs-names"

DOC_FILE = os.path.join("docs", "observability.md")

EMIT_FUNCS = ("inc", "observe", "set_gauge", "span", "counter",
              "gauge", "histogram")

# a metric/span name: lowercase segments joined by '.' or '/'
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*([./][a-z0-9_]+)+$")
_WILD_RE = re.compile(r"^[a-z][a-z0-9_]*\.\*$")
_TICK_RE = re.compile(r"`([^`]+)`")
# backticked tokens that are python-API / file references, not metric
# names: module attribute paths and anything with a file extension
_API_PREFIXES = ("obs.", "lgb.", "jax.", "np.", "numpy.",
                 "lightgbm_tpu.", "self.", "config.", "sys.", "os.")
_FILE_SUFFIXES = (".py", ".md", ".sh", ".json", ".jsonl", ".log",
                  ".cpp", ".hpp", ".h", ".rst", ".csv", ".txt",
                  ".conf", ".dev")


def emitted_names(sources: SourceSet) -> Set[Tuple[str, str, int]]:
    """(name, file, line) for every constant-name emission call."""
    out = set()
    for rel, tree in sources.items():
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if call_name(node) not in EMIT_FUNCS or not node.args:
                continue
            s = const_str(node.args[0])
            if s and _NAME_RE.match(s):
                out.add((s, rel, node.lineno))
    return out


def mentioned_names(sources: SourceSet) -> Set[str]:
    """Every constant string ANYWHERE in code shaped like a metric
    name — the loose set the docs→code direction checks against (it
    catches names that reach the registry through dicts/tuples, e.g.
    the slo.* gauges derived in SloTracker.compute)."""
    out = set()
    for _rel, tree in sources.items():
        for node in ast.walk(tree):
            s = const_str(node)
            if s and _NAME_RE.match(s):
                out.add(s)
    return out


def documented_names(root: str) -> Tuple[Set[str], Set[str]]:
    """(exact names, wildcard prefixes) from the doc catalogue."""
    path = os.path.join(root, DOC_FILE)
    if not os.path.exists(path):
        return set(), set()
    text = open(path, encoding="utf-8").read()
    exact: Set[str] = set()
    wild: Set[str] = set()
    for tok in _TICK_RE.findall(text):
        tok = tok.strip()
        # strip a {label=...} suffix: slo.breached{slo=...} -> slo.breached
        tok = re.sub(r"\{[^}]*\}$", "", tok)
        if ("(" in tok or " " in tok or "=" in tok
                or tok.startswith(_API_PREFIXES)
                or tok.endswith(_FILE_SUFFIXES)):
            continue
        if _WILD_RE.match(tok):
            wild.add(tok[:-2])
        elif _NAME_RE.match(tok):
            exact.add(tok)
    return exact, wild


def _covered(name: str, exact: Set[str], wild: Set[str]) -> bool:
    return name in exact or any(name == w or name.startswith(w + ".")
                                for w in wild)


def check(sources: SourceSet) -> List[Finding]:
    exact, wild = documented_names(sources.root)
    out: List[Finding] = []
    emitted = emitted_names(sources)
    emitted_set = {n for n, _f, _l in emitted}
    reported: Set[str] = set()
    for name, rel, line in sorted(emitted):
        if not _covered(name, exact, wild) and name not in reported:
            reported.add(name)
            out.append(Finding(
                NAME, rel, line, f"undocumented:{name}",
                f"metric/span `{name}` is emitted here but missing "
                f"from the docs/observability.md catalogue"))
    mentioned = mentioned_names(sources) | emitted_set
    for name in sorted(exact):
        if name not in mentioned:
            out.append(Finding(
                NAME, DOC_FILE, 0, f"unemitted:{name}",
                f"docs/observability.md catalogues `{name}` but no "
                f"code emits (or even mentions) it — fix the doc or "
                f"the emission"))
    return out
