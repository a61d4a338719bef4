"""The zero-silently-ignored-params contract (VERDICT r2 item 6).

Every entry in the config table must be one of:
  1. consumed somewhere in package source (outside config.py's table),
  2. declared UNIMPLEMENTED (warns when set to a non-default value), or
  3. declared DISSOLVED (an implementation hint whose correct TPU/XLA
     behavior is "no action", with a recorded rationale).

Reference: upstream honors every documented param via config_auto.cpp
(SURVEY.md:88) — this test is the enforcement mechanism for that parity
claim at param granularity."""
import inspect
import io
import pathlib
import re
import tokenize

import numpy as np
import pytest

import lightgbm_tpu.config as C


def _strip_comments_and_docstrings(source: str) -> str:
    """Drop COMMENT tokens and statement-level strings (docstrings) so a
    param mentioned only in prose cannot pass the audit. String literals
    inside expressions survive — ``params["max_bin"]`` /
    ``getattr(cfg, "max_bin")`` are real consumption."""
    out = []
    prev = None
    in_docstring = False
    toks = tokenize.generate_tokens(io.StringIO(source).readline)
    for tok in toks:
        if tok.type == tokenize.COMMENT:
            continue
        if tok.type == tokenize.STRING:
            # statement-level string, or a continuation segment of one
            # (implicit concatenation: "a" "b" tokenizes as two STRINGs)
            if in_docstring or prev in (
                    None, tokenize.NEWLINE, tokenize.NL, tokenize.INDENT,
                    tokenize.DEDENT):
                in_docstring = True
                continue
        elif tok.type != tokenize.NL:
            in_docstring = False
        if tok.type != tokenize.NL:
            prev = tok.type
        out.append(tok.string)
    return " ".join(out)


def _package_source_without_param_table() -> str:
    pkg = pathlib.Path(C.__file__).parent
    src = []
    for p in sorted(pkg.rglob("*.py")):
        if p.name == "config.py":
            continue
        src.append(_strip_comments_and_docstrings(p.read_text()))
    # config.py consumes some params itself (CheckParamConflict fixups),
    # but its _PARAMS table mentions every name — include only the
    # consuming code, not the table
    src.append(_strip_comments_and_docstrings(
        inspect.getsource(C.Config._post_process)))
    src.append(_strip_comments_and_docstrings(inspect.getsource(
        type(C.Config({"verbosity": -1})).num_tree_per_iteration.fget)))
    return "\n".join(src)


def test_every_param_consumed_warned_or_dissolved():
    src = _package_source_without_param_table()
    unaccounted = []
    for name in C.Config.param_names():
        if name in C.UNIMPLEMENTED_PARAMS:
            continue
        if name in C.DISSOLVED_PARAMS:
            continue
        if not re.search(rf"\b{name}\b", src):
            unaccounted.append(name)
    assert not unaccounted, (
        f"params neither consumed in source nor declared in "
        f"UNIMPLEMENTED_PARAMS/DISSOLVED_PARAMS: {unaccounted}")


def test_tables_are_disjoint_and_valid():
    names = set(C.Config.param_names())
    unimp = set(C.UNIMPLEMENTED_PARAMS)
    diss = set(C.DISSOLVED_PARAMS)
    assert unimp <= names, unimp - names
    assert diss <= names, diss - names
    assert not (unimp & diss)
    # every dissolved rationale is a real sentence, not a stub
    for k, v in {**C.UNIMPLEMENTED_PARAMS, **C.DISSOLVED_PARAMS}.items():
        assert len(v) > 15, (k, v)


# ---------------------------------------------------------------------------
# names that left the table (PR 31) are unknown names like any other
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,value", [
    ("tpu_hist_mode", "rebuild"),
    ("tpu_use_pallas", False),
    ("tpu_rows_per_block", 1024),
])
def test_removed_option_is_an_unknown_name(name, value):
    """No alias, no shim: the name passes through ``Config.update`` to
    ``raw_params`` as any unrecognized key does, nothing reads it, and
    the model trained with it set is the model trained without it."""
    import lightgbm_tpu as lgb
    assert name not in C.Config.param_names()
    unknown = C.Config({"verbosity": -1, "some_unknown_key": 1})
    cfg = C.Config({"verbosity": -1, name: value})
    assert not hasattr(cfg, name)
    assert cfg.raw_params[name] == value
    assert unknown.raw_params["some_unknown_key"] == 1
    rng = np.random.default_rng(7)
    X = rng.normal(size=(1500, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    models = [lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=4)
              .model_to_string() for p in (base, {**base, name: value})]
    assert models[0] == models[1]


# ---------------------------------------------------------------------------
# option census: every tpu_* option has a caller that sets it
# ---------------------------------------------------------------------------
def test_every_tpu_option_is_set_somewhere():
    """A ``tpu_*`` option that no test, no benchmark configuration or
    entry and not ``chip_smoke.py`` ever sets has one value in use: it
    should be a constant (two such options went in PR 31). "Set" means
    the quoted name or a keyword argument in code, not prose."""
    root = pathlib.Path(C.__file__).parent.parent
    files = [p for p in (root / "tests").rglob("*.py")
             if p.name != pathlib.Path(__file__).name]
    files += sorted((root / "benchmark" / "configs").glob("*.json"))
    files += sorted((root / "benchmark" / "entries").glob("*.py"))
    files.append(root / "chip_smoke.py")
    text = "\n".join(
        _strip_comments_and_docstrings(p.read_text())
        if p.suffix == ".py" else p.read_text() for p in files)
    unset = [n for n in C.Config.param_names() if n.startswith("tpu_")
             and not re.search(rf"[\"']{n}[\"'=]|\b{n}\s*=", text)]
    assert not unset, f"tpu_* options nothing sets: {unset}"
