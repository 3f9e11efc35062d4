"""Reduction from a profiler trace and program spans to busy, idle, gaps,
on a trace recorded on a TPU v5e (the served dense stage, 20 calls)."""
import pathlib

import numpy as np
import pytest

from chipbench import devtrace

TRACE = pathlib.Path(__file__).parent / "data" / "v5e_dense.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    # The recorded window annotation spans 65,841,742 ns on the host.
    return devtrace.reduce_xplane(TRACE, (100.0, 100.0 + 0.065841742),
                                  annotation="bench.window")


def test_window_and_busy(recorded):
    assert recorded.window_s == pytest.approx(0.065841742, abs=1e-9)
    busy = recorded.busy[0]
    # Disjoint, sorted, inside the window.
    assert all(a[1] <= b[0] for a, b in zip(busy, busy[1:]))
    assert busy[0][0] >= recorded.window[0]
    assert busy[-1][1] <= recorded.window[1]
    # The union is below the plain sum of op durations (async copies
    # overlap the fusions) and above the longest op.
    assert 6.0e-5 < recorded.busy_s < 7.0339e-5
    assert 0.99 < 1 - recorded.busy_s / recorded.window_s < 1


def test_ops_named_by_module(recorded):
    top = max(recorded.ops, key=recorded.ops.get)
    assert top == "jit__dense_fn/fusion"
    assert sum(recorded.ops.values()) >= recorded.busy_s


def test_merge_clip_complement():
    m = devtrace.merge([(3, 4), (0, 1), (0.5, 2), (5, 6)])
    assert m == [(0, 2), (3, 4), (5, 6)]
    assert devtrace.clip(m, 1, 5.5) == [(1, 2), (3, 4), (5, 5.5)]
    assert devtrace.complement(m, -1, 7) == [(-1, 0), (2, 3), (4, 5), (6, 7)]


def test_idle_time_by_innermost_span():
    spans = [("admit", 0.0, 4.0), ("probe", 1.0, 2.0), ("dense", 6.0, 7.0)]
    idle = [(0.5, 1.5), (3.0, 6.5), (8.0, 9.0)]
    got = devtrace.label_time(spans, idle)
    assert got == pytest.approx({"admit": 0.5 + 1.0, "probe": 0.5,
                                 "untraced": 2.0 + 1.0, "dense": 0.5})
    total = sum(e - s for s, e in idle)
    assert sum(got.values()) == pytest.approx(total)


def _inside(x, intervals):
    """Bool per x: inside one of sorted disjoint ``intervals``."""
    a = np.asarray([s for s, _ in intervals])
    b = np.asarray([e for _, e in intervals])
    k = np.searchsorted(a, x, side="right") - 1
    return (k >= 0) & (x < b[np.maximum(k, 0)])


def test_idle_time_random_matches_sampling():
    rng = np.random.default_rng(3)
    outer, inner, t = [], [], 0.0
    for _ in range(200):
        s = t + rng.random()
        e = s + rng.random()
        outer.append((s, e))
        inner.append((s + 0.25 * (e - s), s + 0.5 * (e - s)))
        t = e
    spans = [("outer", *o) for o in outer] + [("inner", *i) for i in inner]
    idle = devtrace.merge((x, x + rng.random() * 0.3)
                          for x in rng.uniform(0, t, 300))
    got = devtrace.label_time(spans, idle)
    x = np.linspace(0, t, 2_000_001)[:-1] + t / 4_000_000
    dt = t / 2_000_000
    idle_x = _inside(x, idle)
    in_inner, in_outer = _inside(x, inner), _inside(x, outer)
    want = {"inner": np.sum(idle_x & in_inner) * dt,
            "outer": np.sum(idle_x & in_outer & ~in_inner) * dt,
            "untraced": np.sum(idle_x & ~in_outer) * dt}
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=0.002, abs=1e-3)
