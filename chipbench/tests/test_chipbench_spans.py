"""The span readers (``chipbench/spans.py``) on synthetic spans and steps:
a mean over the spans that start in the window, and the step time that no
span covers."""
import numpy as np
import pytest

from chipbench import cells, driver, harness


def _window(spans=(), steps=()):
    d = driver.Drive(
        arrival=np.zeros(1), submit=np.zeros(1), retire=np.full(1, np.nan),
        scores=np.full(1, np.nan),
        steps=np.asarray(steps, float).reshape(-1, 2), error=None)
    cfg = cells.load_config(cells.BENCH_DIR / "configs" / "dlrm-flexemr.json")
    return harness.Window(
        seconds=10.0, marks=(0.0, 10.0), start={}, end={}, drive=d,
        in_window=np.array([True]), spans=list(spans), device=None,
        end_to_end={}, config=cfg, chips=1, peak={})


SPAN_READERS = [("poll_ms", "poll"), ("heat_ms", "heat"),
                ("refresh_ms", "refresh"), ("account_ms", "account")]


@pytest.mark.parametrize("metric,name", SPAN_READERS)
def test_mean_of_spans_in_the_window(metric, name):
    spans = [(name, 1.0, 1.002), (name, 4.0, 4.006), ("admit", 2.0, 3.0)]
    assert cells.load_reader(metric)(_window(spans)) == pytest.approx(4.0)


@pytest.mark.parametrize("metric,name", SPAN_READERS)
def test_spans_starting_outside_the_window_are_left_out(metric, name):
    # One starts before the open and one at the close: neither counts;
    # the one running past the close counts whole.
    spans = [(name, -0.5, 0.5), (name, 9.9, 10.9), (name, 10.0, 10.1)]
    assert cells.load_reader(metric)(_window(spans)) == pytest.approx(1000.0)


@pytest.mark.parametrize("metric,name", SPAN_READERS)
def test_no_span_reads_nothing(metric, name):
    spans = [("admit", 1.0, 2.0), ("dense", 2.5, 3.0), (name, 11.0, 12.0)]
    assert cells.load_reader(metric)(_window(spans)) is None


STEPS = [(1.0, 3.0), (4.0, 6.0), (9.0, 11.0)]  # 5 s inside the window
SPANS = [("poll", 1.0, 1.1), ("admit", 1.1, 2.0), ("probe", 1.2, 1.8),
         ("account", 2.0, 2.5), ("dense", 4.5, 5.0), ("heat", 5.0, 5.25),
         ("lookup_stall", 9.5, 10.5), ("refresh", 10.6, 11.0)]


@pytest.mark.parametrize("metric", ["step_residual_share",
                                    "step_residual_share.sat"])
def test_step_residual_subtracts_every_span(metric):
    # In the window: poll 0.1, admit 0.9 (the probe inside it), account
    # 0.5, dense 0.5, heat 0.25, lookup_stall 0.5 (to the close) = 2.75 of
    # the 5 s in steps; the refresh starts after the close.
    w = _window(SPANS, STEPS)
    assert cells.load_reader(metric)(w) == pytest.approx(100.0 * 2.25 / 10)
    # step_untraced_share subtracts only admit, lookup_stall and dense.
    assert cells.load_reader("step_untraced_share")(w) == pytest.approx(
        100.0 * 3.1 / 10)


def test_step_residual_without_steps_or_spans_reads_nothing():
    assert cells.load_reader("step_residual_share")(_window(SPANS)) is None
    assert cells.load_reader("step_residual_share")(
        _window((), STEPS)) is None
