"""The knee rule: the highest offered rate whose backlog grew by at most one
largest bucket over the window."""
import pytest

from chipbench.sweep import knee, rates_arg


def test_knee_on_backlog_series():
    pts = [(1500, 10, 40), (1750, 30, 500), (2000, 40, 1064),
           (2250, 50, 1075), (2500, 60, 9000)]
    assert knee(pts, 1024) == 2000


def test_knee_takes_highest_holding_rate():
    assert knee([(1000, 0, 5000), (1250, 0, 100)], 1024) == 1250


@pytest.mark.parametrize("pts", [[], [(3000, 0, 2000)]])
def test_no_knee(pts):
    assert knee(pts, 1024) is None


def test_rates_spaced_as_asked():
    assert rates_arg("1500:2500:250") == [1500, 1750, 2000, 2250, 2500]
