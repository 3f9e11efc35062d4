"""``correct`` on a whole run, without a chip: sound runs pass, and a run
with the served path broken underneath, or the control put in its place,
comes out not correct.  A tiny DLRM on the CPU stands in for the cells."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells, control, harness

DATA = cells.BENCH_DIR / "tests" / "data"
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def cell():
    bench = cells.load_benchmark()
    return cells.Cell(
        "tiny", 1, cells.load_config(DATA / "tiny-config.json"),
        cells.load_traffic(DATA / "tiny-traffic.json"),
        tuple(map(cells._metric, bench["end_to_end"])),
        tuple(map(cells._metric, bench["per_layer"])))


def _run(cell, trace=False, seed=SEED):
    return harness.run_cell(cell, seed, 1.0, trace, time.perf_counter(),
                            jax.devices())


def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 200
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"p50_ms", "p99_ms", "served_rps", "setup_s"}
    gap = r["checks"]["score_gap"]
    assert gap["value"] < gap["limit"]
    assert r["info"]["compiles_in_window"] == 0


def test_traced_run_reports_layers(cell):
    r = _run(cell, trace=True)
    assert r["correct"]
    for name in ("queue_wait_ms", "probe_ms", "merge_ms", "dense_ms",
                 "hit_rate", "wire_bytes_per_req", "step_untraced_share",
                 "driver_lag_ms"):
        assert name in r["metrics"], name
    # No TPU plane on the CPU: the device metrics are left out, not 0.
    assert "device_idle_share" not in r["metrics"]


def _broken_dense(monkeypatch, how):
    from repro.runtime.serving import FlexEMRServer

    sound = FlexEMRServer._dense_fn

    def dense_fn(self, pooled, dense):
        return how(sound(self, pooled, dense))
    monkeypatch.setattr(FlexEMRServer, "_dense_fn", dense_fn)


@pytest.mark.parametrize("fault", ["one_answer_altered",
                                   "answers_to_wrong_requests",
                                   "one_row_wrong"])
def test_broken_served_path_is_not_correct(cell, monkeypatch, fault):
    if fault == "one_answer_altered":
        _broken_dense(monkeypatch, lambda s: s.at[0].multiply(1.001))
    elif fault == "answers_to_wrong_requests":
        _broken_dense(monkeypatch, lambda s: jnp.roll(s, 1))
    else:
        from repro.hotcache.miss_path import PendingTieredLookup

        sound = PendingTieredLookup.wait

        def wait(self, timeout=None):
            out = np.array(sound(self, timeout))
            out[0, 0] = out[0, 1]  # request 0's first field: another row
            return out
        monkeypatch.setattr(PendingTieredLookup, "wait", wait)
    r = _run(cell)
    assert not r["correct"]
    assert r["checks"]["score_gap"]["value"] > r["checks"]["score_gap"]["limit"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_is_not_correct(cell, seed):
    """The reference at ``high`` (three bfloat16 passes) in the program's
    place reads above the limit that sound runs stay under."""
    gap = control.control_gap(cell.config, cell.traffic, seed, 1.0)
    assert gap > cell.config["correctness"]["score_gap_limit"]
