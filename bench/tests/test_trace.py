"""The trace reducer on a small trace recorded on a TPU v5 lite: three
runs of one jitted program (a Pallas kernel and a dot), each in a host
span ``job`` and each followed by ``gap`` (a 10 ms sleep)."""
import os

import pytest

from bench import trace

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_file(TINY)


def test_busy_is_the_union_of_program_runs(summary):
    # Three runs of jit_tiny, 2,360 ns each on the device (7,080 ns).
    assert summary.busy_s == pytest.approx(7080e-9)
    # No "window" span: the window is the extent of the device events.
    assert summary.window_s == pytest.approx((71601464 - 49500200) * 1e-9)
    assert summary.idle_frac == [pytest.approx(1 - 7080 / 22101264)]


def test_top_ops_by_self_time(summary):
    names = [n for n, _ in summary.top_ops]
    assert names == ["jit_tiny/convolution_reduce_fusion", "jit_tiny/tiny.1"]
    assert summary.top_ops[0][1] == pytest.approx(4656e-9)
    assert summary.kernel_s("tiny.1") == pytest.approx(2398e-9)


def test_idle_gaps_are_named_by_host_span(summary):
    assert [n for n, _ in summary.idle_gaps] == ["gap", "gap"]
    assert all(s > 0.01 for _, s in summary.idle_gaps)
    assert summary.collective_s == [0.0]


def test_self_time_subtracts_nested_events():
    ops, selfs = trace._self_times([(0, 10, "%while.1 = ..."),
                                    (1, 4, "%a = ..."), (5, 7, "%b = ...")])
    assert dict(zip((n for _, _, n in ops), selfs)) == {
        "%while.1 = ...": 5, "%a = ...": 3, "%b = ...": 2}
