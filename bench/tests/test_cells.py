"""Every cell of BENCHMARK.json loads by name from its files, and the
command refuses to run without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, run

BM = harness.benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    cell = harness.load_cell(name)
    assert cell.config["name"] == next(
        w["config"] for w in BM["workloads"] if w["name"] == name)
    assert os.path.exists(os.path.join(harness.BENCH, "kinds",
                                       f"{cell.kind}.py"))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_module("metrics", m["name"]).read)
    assert cell.config["limits"], "every compared number has a limit"


def test_benchmark_files_lie_under_paths():
    assert BM["paths"] == ["bench"]
    for c in BM["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"]


def test_command_without_tpu_exits_nonzero(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "3", "--seconds", "1"])
    err = capsys.readouterr()
    assert rc != 0
    assert "no TPU chip" in err.err
    assert err.out == ""


def test_unknown_workload_exits_nonzero(capsys):
    rc = run.main(["--workload", "no-such-cell", "--seed", "3",
                   "--seconds", "1"])
    assert rc != 0 and capsys.readouterr().out == ""


def test_bare_checkout_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and bench/ has no program."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
