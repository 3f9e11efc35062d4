"""The payload generator: deterministic in the seed, true to its key law."""
import numpy as np
import pytest

from chipbench import cells, traffic

CONFIG = cells.load_config(cells.BENCH_DIR / "configs" / "dlrm-flexemr.json")
ZIPF = cells.load_traffic(cells.BENCH_DIR / "traffic" / "flexemr-zipf-tail.json")
UNIFORM = {k: v for k, v in ZIPF.items() if k != "alpha"} | {
    "key_law": "uniform"}


@pytest.mark.parametrize("mix", [ZIPF, UNIFORM], ids=["zipf", "uniform"])
def test_same_seed_same_traffic(mix):
    seed = 2**31 + 12345
    a = traffic.make_traffic(seed, CONFIG, mix, 0.5, rate=2000)
    b = traffic.make_traffic(seed, CONFIG, mix, 0.5, rate=2000)
    c = traffic.make_traffic(seed + 1, CONFIG, mix, 0.5, rate=2000)
    for k in ("t", "indices", "mask", "dense"):
        assert np.array_equal(getattr(a, k), getattr(b, k))
    assert not np.array_equal(a.indices, c.indices)
    # Every seed offers the same number of requests, in order.
    assert len(a) == len(c) == 1000
    assert np.all(np.diff(a.t) >= 0) and a.t[0] >= 0 and a.t[-1] < 0.5


def test_seeds_past_32_bits_differ():
    a = traffic.make_traffic(5, CONFIG, ZIPF, 0.1, rate=1000)
    b = traffic.make_traffic(5 + 2**32, CONFIG, ZIPF, 0.1, rate=1000)
    assert not np.array_equal(a.indices, b.indices)


def test_zipf_ranks_follow_the_law():
    rng = np.random.default_rng(0)
    rows, alpha = 1_000_000, 1.05
    r = traffic.zipf_ranks(rng, rows, 400_000, alpha)
    a1 = 1.0 - alpha
    for k in (1, 10, 1000, 100_000):
        want = ((k + 1) ** a1 - 1.0) / (rows ** a1 - 1.0)
        assert abs(np.mean(r < k) - want) < 0.004, k
    assert r.min() >= 0 and r.max() < rows


def test_uniform_ids_moments():
    rng = np.random.default_rng(1)
    rows = 10_000
    ids = traffic.field_ids(rng, rows, 1, 200_000, UNIFORM)
    assert ids.min() >= 0 and ids.max() < rows
    assert abs(ids.mean() - (rows - 1) / 2) < 0.01 * rows
    assert abs(ids.var() - (rows ** 2 - 1) / 12) < 0.02 * rows ** 2 / 12


def test_full_bags():
    tr = traffic.make_traffic(7, CONFIG, ZIPF, 2.0, rate=2000)
    nnz = np.asarray([t["nnz"] for t in CONFIG["tables"]])
    # Every bag holds its field's nnz ids, and nothing past them.
    assert np.array_equal(tr.mask.sum(axis=2),
                          np.broadcast_to(nnz, tr.mask.shape[:2]))
    assert np.all(tr.mask[:, :, 1:] <= tr.mask[:, :, :-1])
    rows = np.asarray([t["rows"] for t in CONFIG["tables"]])
    assert np.all(tr.indices < rows[None, :, None])
    assert np.all(tr.indices[~tr.mask] == 0)


def test_hot_set_takes_most_lookups():
    """The share of a field's lookups on its hottest 10% of rows that the
    mix's source states (79-92% over these table sizes)."""
    tr = traffic.make_traffic(3, CONFIG, ZIPF, 4.0, rate=2000)
    for f, t in enumerate(CONFIG["tables"]):
        ids = tr.indices[:, f, :t["nnz"]]
        share = np.mean(ids < 0.1 * t["rows"])
        assert 0.76 < share < 0.93, (t["name"], share)


def test_dense_features_uniform():
    tr = traffic.make_traffic(9, CONFIG, UNIFORM, 4.0, rate=2000)
    assert tr.dense.dtype == np.float32
    assert tr.dense.min() >= 0.0 and tr.dense.max() < 1.0
    assert abs(tr.dense.mean() - 0.5) < 0.01
    assert abs(tr.dense.var() - 1 / 12) < 0.005
