"""The compulsory-work counts behind ``mrg_roofline``, against hand
arithmetic for the three cells' shapes, and the peak table."""
import pytest

from bench import work

D, K = 768, 1024
ROWS = 2_097_152          # per chip in all three cells


@pytest.mark.parametrize("cell,union", [
    ("curation-d768.mrg-device", 16 * 1024),       # 16 simulated machines
    ("curation-d768-x4.mrg-mesh", 4 * 1024),       # one machine per chip
])
def test_mrg_job_counts(cell, union):
    w = work.mrg_job(ROWS, D, K, union)
    # k passes over the rows and over the union, one radius read.
    assert w.bytes == 4 * 768 * (1024 * (2_097_152 + union) + 2_097_152)
    assert w.flops == (3 * 768 * 1024 * (2_097_152 + union)
                       + 2 * 1024 * 768 * 2_097_152)


def test_mrg_device_floor_is_hbm_bound():
    w = work.mrg_job(ROWS, D, K, 16 * 1024)
    t, bound = work.floor_s(w, "TPU v5 lite")
    assert bound == "hbm"
    # 6.655e12 bytes at 819 GB/s.
    assert t == pytest.approx(6_655_051_825_152 / 819e9)
    assert 8.12 < t < 8.13


def test_v5e_peaks():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert work.peaks("TPU v5 lite")["flops_bf16"] == 197e12


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
