"""The repo-wide per-test time limit (``conftest.py`` at the repo root)."""
from pathlib import Path

ROOT_CONFTEST = Path(__file__).resolve().parents[1] / "conftest.py"


def test_test_past_its_limit_fails_and_the_run_goes_on(pytester):
    pytester.makeconftest(ROOT_CONFTEST.read_text())
    pytester.makepyfile("""
        import time

        import pytest

        @pytest.mark.time_limit(2)
        def test_sleeps_past_its_limit():
            time.sleep(60)

        def test_after_it():
            pass
    """)
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider")
    result.assert_outcomes(passed=1, failed=1)
    result.stdout.fnmatch_lines(
        ["*test_sleeps_past_its_limit ran past its time limit of 2 s*"])
    assert result.duration < 30
