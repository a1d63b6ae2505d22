"""End-to-end dry-run smoke: one real cell lowers + compiles on the
production mesh (512 placeholder devices) in a subprocess, and the
artifact contains all roofline inputs.

This covers deliverable (e) in-suite; the full 64-cell sweep runs via
experiments/run_sweep.sh.
"""
import json
import os
import subprocess
import sys

import pytest


@pytest.mark.parametrize("arch,shape,mesh", [
    ("qwen2_0_5b", "decode_32k", "single"),
    ("mamba2_370m", "long_500k", "multi"),
])
def test_dryrun_cell_compiles(tmp_path, arch, shape, mesh):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", mesh, "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=240)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    mesh_name = "pod16x16" if mesh == "single" else "pod2x16x16"
    path = tmp_path / mesh_name / f"{arch}__{shape}.json"
    rec = json.loads(path.read_text())
    assert rec["chips"] == (256 if mesh == "single" else 512)
    assert rec["memory_analysis"]["temp_size_in_bytes"] >= 0
    assert rec["roofline_terms_s"]["memory_s"] > 0
    assert "collectives_per_device" in rec
