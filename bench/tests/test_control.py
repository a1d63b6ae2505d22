"""A precision drop, at a size a test run holds: with the plain reference
put in the program's place, a run must come out not correct by the
harness's own comparison, and a sound run on the same seed correct. On the
chip the control is the HIGH dot mode (three bf16 passes; readings in
PERF.md); a CPU computes every dot in f32, so here the reference's
one-pass bf16 dot stands for it."""
import jax

from bench import control, run

run.add_paths()
SEED = 2**35 + 1
MRG = dict(n=8192, d=768, k=32, clusters=4, singleton_every=512)


def _run():
    return run.run_cell("curation-d768.mrg-device", SEED, 0.2, False,
                        devices=jax.devices()[:1], overrides=MRG)


def test_mrg_control_fails_and_program_passes():
    sound = _run()
    assert sound["correct"] is True, sound["checks"]
    with control.reference_in_place("default"):
        line = _run()
    assert line["correct"] is False, line["checks"]
    failed = [k for k, c in line["checks"].items()
              if c["value"] > c["limit"]]
    assert "r2_gap_eps" in failed, line["checks"]
