"""Dense operations per request, against a count by hand."""
import pytest

from chipbench import cells, model


def _cfg(name):
    if name == "tiny":
        return cells.load_config(cells.BENCH_DIR / "tests" / "data"
                                 / "tiny-config.json")
    return cells.load_config(cells.BENCH_DIR / "configs" / f"{name}.json")


@pytest.mark.parametrize("name,macs", [
    # bottom 13-512-256-64; 27 vectors, 378 dots incl. self, of dim 64;
    # top (378 + 64)-512-256-1.
    ("dlrm-flexemr", 13 * 512 + 512 * 256 + 256 * 64 + 378 * 64
     + 442 * 512 + 512 * 256 + 256 * 1),
    # bottom 13-32-16; 4 vectors, 10 dots incl. self, of dim 16;
    # top (10 + 16)-32-1.
    ("tiny", 13 * 32 + 32 * 16 + 10 * 16 + 26 * 32 + 32 * 1),
])
def test_dense_flops_by_hand(name, macs):
    assert model.dense_flops_per_request(_cfg(name)) == 2 * macs


def test_without_self_dots():
    cfg = dict(_cfg("dlrm-flexemr"), interaction_itself=False)
    assert model.top_in(cfg) == 351 + 64
