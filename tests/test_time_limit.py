"""The per-test time limit of conftest.py (`_time_limit`): armed while a
test runs, gone once it is torn down, so that an alarm left behind can
never fail a later test."""
import signal

import pytest

import conftest


@pytest.fixture(scope="module", autouse=True)
def _no_alarm_left_behind():
    """Module scope: set up before, and torn down after, every
    function-scoped fixture of the tests below, `_time_limit` included."""
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    before = signal.getsignal(signal.SIGALRM)
    yield
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is before


def test_time_limit_is_armed_around_a_test():
    left, again = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= conftest.TEST_TIME_LIMIT
    assert 0 < again < conftest.TEST_TIME_LIMIT
    assert signal.getsignal(signal.SIGALRM) not in (
        signal.SIG_DFL, signal.SIG_IGN, None)
