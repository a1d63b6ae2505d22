"""Repo-wide pytest setup: a per-test time limit, for ``tests/`` and
``bench/tests/`` alike.

A test's setup and its call each get ``DEFAULT_LIMIT_S`` seconds of wall
time; ``@pytest.mark.time_limit(seconds)`` overrides that for one test.
Past its limit a SIGALRM fails the test with its name and limit, and the
run goes on to the next test. A hang inside C code that never returns to
the interpreter cannot see the alarm, so ``faulthandler`` dumps every
thread's stack and ends the process ``_BACKSTOP_MARGIN_S`` later.

The alarm is armed only in the main thread, where pytest (and each xdist
worker) runs its tests.
"""
import contextlib
import faulthandler
import signal
import sys
import threading

import pytest

pytest_plugins = ("pytester",)

DEFAULT_LIMIT_S = 300.0     # twice the slowest test seen on CPU
_BACKSTOP_MARGIN_S = 60.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        f"time_limit(seconds): fail the test once its setup or call runs "
        f"past `seconds` of wall time (default {DEFAULT_LIMIT_S:g})")


@contextlib.contextmanager
def _time_limit(item):
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else DEFAULT_LIMIT_S

    def on_alarm(signum, frame):
        pytest.fail(f"{item.nodeid} ran past its time limit of {limit:g} s")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + _BACKSTOP_MARGIN_S, exit=True,
                                      file=sys.__stderr__)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _time_limit(item):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _time_limit(item):
        return (yield)
