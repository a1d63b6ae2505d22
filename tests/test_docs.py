"""Docs stay runnable: doctests on the public surface, README snippets,
and the quickstart example.

Three rot-prevention contracts (the docs satellite of the sharded-source
PR):

  * every doctest in the public API modules (``mrg`` / ``eim`` /
    ``gonzalez`` / ``select_coreset`` / the sources) executes and matches;
  * every ``python`` code block in README.md executes top-to-bottom in one
    shared namespace (the quickstart snippets build on each other);
  * ``examples/quickstart.py`` runs end to end (small ``--n``) — its
    internal bitwise assertions double as a parity check.
"""
import doctest
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCTEST_MODULES = [
    "repro.core.mrg",
    "repro.core.gonzalez",
    "repro.core.eim",
    "repro.core.coreset",
    "repro.core.outliers",
    "repro.data.source",
]


@pytest.mark.parametrize("modname", DOCTEST_MODULES)
def test_public_api_doctests(modname):
    mod = __import__(modname, fromlist=["_"])
    result = doctest.testmod(mod, verbose=False)
    assert result.attempted > 0, f"{modname} lost its doctests"
    assert result.failed == 0, f"{modname}: {result.failed} doctest(s) failed"


def _readme_blocks():
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    return re.findall(r"```python\n(.*?)```", text, flags=re.S)


def test_readme_python_blocks_execute():
    blocks = _readme_blocks()
    assert len(blocks) >= 3, "README lost its quickstart snippets"
    ns: dict = {}
    for i, block in enumerate(blocks):
        try:
            exec(compile(block, f"README.md[block {i}]", "exec"), ns)
        except Exception as err:  # pragma: no cover - failure reporting
            raise AssertionError(
                f"README.md python block {i} failed: {err}\n{block}") from err


def test_quickstart_example_runs():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "quickstart.py"),
         "--n", "20000"],
        capture_output=True, text=True, env=env, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("GON", "MRG", "EIM", "out-of-core", "sharded"):
        assert tag in out.stdout, f"quickstart output lost its {tag} row"


def test_coreset_curation_example_runs():
    """The curation example end to end (small --n): its internal
    assertions double as checks that curated ≤ random under the same
    streamed fold, weights are conserved, and the outlier pass excludes
    the planted contamination."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "coreset_curation.py"),
         "--n", "4000"],
        capture_output=True, text=True, env=env, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    for tag in ("curated", "random", "weighted coreset", "kz_center"):
        assert tag in out.stdout, f"curation output lost its {tag} row"
