"""Repo hygiene: no bytecode artifacts, no resurrected legacy API names.

A tracked ``__pycache__`` directory once shadowed a real package at
import time (``src/repro/serving/__pycache__`` survived a refactor and
Python happily imported the stale ``.pyc``s) — the failure mode is
silent and maddening, so tier-1 fails fast on any tracked bytecode and
on a ``.gitignore`` that stopped covering it.  CI runs the same check
shell-side in the lint job; this test makes it bite locally too.

The legacy-name guard moved into the lint framework
(``repro.analysis.rules.retired_names``, rule id ``retired-names``,
allowlist in ``analysis.toml``); the test here is the thin tier-1
assertion that the rule reports zero findings, with ruff's TID251 bans
as the second line of defense for imports.
"""

import pathlib
import subprocess

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _git(*args):
    return subprocess.run(
        ["git", *args], cwd=REPO, capture_output=True, text=True)


@pytest.fixture(scope="module")
def tracked_files():
    res = _git("ls-files")
    if res.returncode != 0:
        pytest.skip(f"not a git checkout: {res.stderr.strip()}")
    return res.stdout.splitlines()


def test_no_tracked_bytecode(tracked_files):
    bad = [f for f in tracked_files
           if f.endswith(".pyc") or "__pycache__" in f.split("/")]
    assert not bad, (
        f"tracked bytecode artifacts (git rm -r --cached them): {bad}")


def test_gitignore_covers_bytecode_and_bench_scratch():
    patterns = (REPO / ".gitignore").read_text().splitlines()
    for required in ("__pycache__/", "*.pyc", ".jax_cache/", "chiprun_out/"):
        assert required in patterns, (
            f".gitignore lost the {required!r} rule — bytecode/scratch "
            "would start showing up in git status (and risk being added)")


def test_git_would_ignore_a_stray_pyc():
    """The patterns actually work, not just exist: check-ignore must
    match representative paths (never touches the filesystem)."""
    res = _git("check-ignore", "-q", "src/repro/__pycache__/x.pyc")
    if res.returncode == 128:
        pytest.skip(f"git check-ignore unavailable: {res.stderr.strip()}")
    assert res.returncode == 0


def test_no_legacy_forward_path_surfaces():
    """The ``retired-names`` lint rule reports zero findings: new code
    must go through ``paths.available()`` / ``paths.get()``."""
    from repro.analysis.config import AnalysisConfig
    from repro.analysis.lint import run_lint
    from repro.analysis.rules.retired_names import RetiredNamesRule
    findings = run_lint(REPO, [RetiredNamesRule()], AnalysisConfig.load(REPO))
    assert findings == [], "\n".join(f.render() for f in findings)
