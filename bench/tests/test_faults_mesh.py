"""Runs of the four-chip MRG cell on a mesh of four virtual CPU devices,
each in a process of its own (the test process keeps its one-device
view), first as the program is, then broken: with ``all_gather`` and
``pmax`` of the program's executor made to return their operand (the
exchange between chips left out), or with the reference put in the
program's place at one bf16 pass (the control). Each must come out not
correct."""
import json
import os
import subprocess
import sys

SEED = 2**33 + 5

PRELUDE = r"""
import json, sys, types
import jax
from bench import control, run
sys.path.insert(0, "src")
import repro.core.executor as ex

def go():
    line = run.run_cell("curation-d768-x4.mrg-mesh", %d, 0.2, False,
                        devices=jax.devices()[:4],
                        overrides=dict(n=4 * 2048, d=32, k=32, clusters=4,
                                       singleton_every=512))
    return line["correct"], line["checks"]

sound = go()
""" % SEED

NO_EXCHANGE = PRELUDE + r"""
lax = types.SimpleNamespace(**{k: getattr(jax.lax, k) for k in dir(jax.lax)
                               if not k.startswith("__")})
lax.all_gather = lambda x, axis, **kw: x
lax.pmax = lambda x, axis, **kw: x
ex.jax = types.SimpleNamespace(**{k: getattr(jax, k) for k in dir(jax)
                                  if not k.startswith("__")})
ex.jax.lax = lax
jax.clear_caches()
print(json.dumps({"sound": sound, "broken": go()}))
"""

CONTROL = PRELUDE + r"""
with control.reference_in_place("default"):
    broken = go()
print(json.dumps({"sound": sound, "broken": broken}))
"""


def _run(script):
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([root, os.path.join(root, "src")]))
    p = subprocess.run([sys.executable, "-c", script], cwd=root,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_mesh_exchange_left_out_is_not_correct():
    out = _run(NO_EXCHANGE)
    assert out["sound"][0], out
    assert not out["broken"][0], out


def test_mesh_control_is_not_correct():
    out = _run(CONTROL)
    assert out["sound"][0], out
    assert not out["broken"][0], out
    assert out["broken"][1]["r2_gap_eps"]["value"] > \
        out["broken"][1]["r2_gap_eps"]["limit"], out
