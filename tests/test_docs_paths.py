"""The documents name files that exist. One case a document: every
backticked path that starts with a top-level directory of the repo and
ends in a source suffix has to be there. Bare names (``gbdt.py``) and
globs (``tests/test_*.py``) are not paths and are not checked."""
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md"))

_SPAN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(
    r"(?<![\w/.*-])((?:lightgbm_tpu|tests|scripts|tools|benchmark"
    r"|benchmarks|docs)/[\w./-]*\.(?:py|sh|md|json|cpp))(?![\w*])")


@pytest.mark.parametrize("doc", DOCS)
def test_backticked_paths_exist(doc):
    text = (ROOT / doc).read_text()
    paths = {m for span in _SPAN.findall(text)
             for m in _PATH.findall(span)}
    missing = sorted(p for p in paths if not (ROOT / p).exists())
    assert not missing, f"{doc} names files that are not there: {missing}"
