"""A run with the timed path broken underneath must come out not correct.

Each test drives the whole of a run but the look for a chip, on the CPU
at a tiny size, first as the program is (correct) and then with one fault
planted in the program: a step that returns its state unchanged, half of
the work left out, an answer altered where it is produced, and round 2
left out (worse centers with their true radius, which only the radius
check can see). The exchange between chips is in
``test_faults_mesh.py``."""
import jax
import numpy as np
import pytest

from bench import run

run.add_paths()                 # the program under test, from src/
SEED = 2**33 + 5
MRG = dict(n=8192, d=32, k=32, clusters=4, singleton_every=512)


@pytest.fixture
def fresh():
    """A fault planted under a jitted program must not hit the program
    compiled for a sound run, nor leak into the next test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def mrg_run():
    return run.run_cell("curation-d768.mrg-device", SEED, 0.2, False,
                        devices=jax.devices()[:1], overrides=MRG)


def _failed(line):
    return [k for k, c in line["checks"].items() if c["value"] > c["limit"]]


def test_mrg_sound_run_is_correct():
    line = mrg_run()
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1


def _unchanged_pass(points, c, min_d2, **kw):
    return min_d2, min_d2[0], np.int32(0)


def _half(orig):
    def mrg(x, *a, **kw):
        return orig(x[: x.shape[0] // 2], *a, **kw)
    return mrg


def _altered(orig):
    def mrg(*a, **kw):
        res = orig(*a, **kw)
        return res._replace(centers=res.centers.at[0, 0].add(1.0))
    return mrg


def _round2_left_out(orig):
    def mrg(x, k, *, m, **kw):
        from repro.core import covering_radius
        res = orig(x[: x.shape[0] // m], k, m=1, **kw)  # machine 0's GON
        r = covering_radius(x, res.centers)
        return res._replace(radius2=r * r)
    return mrg


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered", "round2_left_out"])
def test_mrg_fault_is_not_correct(fault, monkeypatch, fresh):
    import repro.core
    from repro.kernels import ops
    if fault == "state_unchanged":
        monkeypatch.setattr(ops, "fused_min_argmax", _unchanged_pass)
    elif fault == "half_left_out":
        monkeypatch.setattr(repro.core, "mrg", _half(repro.core.mrg))
    elif fault == "answer_altered":
        monkeypatch.setattr(repro.core, "mrg", _altered(repro.core.mrg))
    else:
        monkeypatch.setattr(repro.core, "mrg",
                            _round2_left_out(repro.core.mrg))
    line = mrg_run()
    assert not line["correct"] and _failed(line), line["checks"]
    if fault == "round2_left_out":         # honest radius, worse centers
        assert _failed(line) == ["radius_gap"], line["checks"]
