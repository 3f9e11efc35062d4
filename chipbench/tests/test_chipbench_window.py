"""The per-layer readers' arithmetic over the window's ends."""
import numpy as np
import pytest

from chipbench import cells, devtrace, driver, harness


def _window(start, end, spans=(), steps=(), device=None, e2e=None):
    n = 4
    d = driver.Drive(
        arrival=np.array([0.0, 1.0, 2.0, 3.0]),
        submit=np.array([0.0, 1.5, 2.25, np.nan]),
        retire=np.full(n, np.nan), scores=np.full(n, np.nan),
        steps=np.asarray(steps, float).reshape(-1, 2), error=None)
    cfg = cells.load_config(cells.BENCH_DIR / "configs" / "dlrm-flexemr.json")
    return harness.Window(
        seconds=10.0, marks=(0.0, 10.0), start=start, end=end, drive=d,
        in_window=np.array([True, True, True, False]), spans=list(spans),
        device=device, end_to_end=e2e or {}, config=cfg, chips=1,
        peak={"bf16_flops_per_s": 197e12})


START = {"requests": 100, "hits": 70, "lookups": 100, "bytes_network": 1000,
         "bytes_request": 10, "queue_wait": (100, 5.0),
         "attr.probe": (10, 0.5), "attr.merge": (10, 0.2),
         "attr.post": (10, 0.1), "attr.dense": (10, 0.03)}
END = {"requests": 300, "hits": 170, "lookups": 300, "bytes_network": 5000,
       "bytes_request": 210, "queue_wait": (300, 45.0),
       "attr.probe": (14, 0.9), "attr.merge": (14, 0.4),
       "attr.post": (10, 0.1), "attr.dense": (14, 0.042)}


@pytest.mark.parametrize("metric,want", [
    ("queue_wait_ms", 1e3 * 40.0 / 200),
    ("probe_ms", 1e3 * 0.4 / 4),
    ("merge_ms", 1e3 * 0.2 / 4),
    ("dense_ms", 1e3 * 0.012 / 4),
    ("hit_rate", 100.0 * 100 / 200),
    ("wire_bytes_per_req", (4000 + 200) / 200),
    ("driver_lag_ms", 1e3 * (0.0 + 0.5 + 0.25) / 3),
])
def test_window_deltas(metric, want):
    assert cells.load_reader(metric)(_window(START, END)) == pytest.approx(want)


def test_nothing_to_read_gives_nothing():
    # No post stage retired in the window, no device trace, no rate.
    w = _window(START, END)
    assert cells.load_reader("post_ms")(w) is None
    assert cells.load_reader("device_idle_share")(w) is None
    assert cells.load_reader("mfu")(w) is None


def test_step_untraced_share():
    steps = [(1.0, 3.0), (4.0, 6.0), (9.0, 11.0)]  # 5 s inside the window
    spans = [("admit", 1.0, 2.0), ("dense", 4.5, 5.0), ("probe", 1.2, 1.8),
             ("lookup_stall", 9.5, 10.5)]  # 2 s of them inside the window
    w = _window(START, END, spans=spans, steps=steps)
    for name in ("step_untraced_share", "step_untraced_share.sat"):
        assert cells.load_reader(name)(w) == pytest.approx(100.0 * 3 / 10)


def test_idle_share_and_mfu():
    dev = devtrace.DeviceTrace((0.0, 10.0), [[(1.0, 1.5), (2.0, 2.1)]], {})
    w = _window(START, END, device=dev, e2e={"served_rps": 3000.0})
    assert cells.load_reader("device_idle_share")(w) == pytest.approx(94.0)
    assert cells.load_reader("mfu")(w) == pytest.approx(
        100.0 * 1_071_872 * 3000 / 197e12)
