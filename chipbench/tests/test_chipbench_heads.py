"""Model heads are files found by a configuration's ``arch``: the DLRM head
gives, bit for bit, what the benchmark's DLRM gave before heads were files,
and a head that exists only as files is picked up with no edit to
``cells``, ``harness`` or ``model``."""
import hashlib
import json
import shutil
import textwrap

import numpy as np
import pytest

from chipbench import cells, harness, model, traffic

DATA = cells.BENCH_DIR / "tests" / "data"
CONFIG = cells.load_config(DATA / "tiny-config.json")
MIX = cells.load_traffic(DATA / "tiny-traffic.json")

# sha256 (first 16 hex digits) of the tiny configuration's weights and of
# the reference scores of its first 200 requests (block 64), as the
# benchmark's model.py computed them before heads were split out.
BEFORE = {
    2**31 + 77: {"table": "d56ad1d29ce57b0a", "bottom": "108c9aa58f225138",
                 "top": "37ded5b6142ee29e", "highest": "d77792d36f052f1f",
                 "high": "b0c116f2f35712d8"},
    12: {"table": "5ae563e9a4649632", "bottom": "108c9aa58f225138",
         "top": "37ded5b6142ee29e", "highest": "3786bc04ee47b827",
         "high": "cd99cbef716aebc4"},
}


def _digest(*arrays) -> str:
    d = hashlib.sha256()
    for a in arrays:
        d.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return d.hexdigest()[:16]


@pytest.mark.parametrize("seed", sorted(BEFORE))
def test_dlrm_head_weights_unchanged(seed):
    w = model.make_weights(CONFIG, seed)
    assert set(w) == {"emb", "bottom", "top"} and set(w["emb"]) == {"table"}
    assert _digest(w["emb"]["table"]) == BEFORE[seed]["table"]
    for part in ("bottom", "top"):
        got = _digest(*(w[part][k] for k in sorted(w[part])))
        assert got == BEFORE[seed][part], part


@pytest.mark.parametrize("precision", ["highest", "high"])
@pytest.mark.parametrize("seed", sorted(BEFORE))
def test_dlrm_head_reference_unchanged(seed, precision):
    tr = traffic.make_traffic(seed, CONFIG, MIX, 1.0)
    n = 200
    scores = model.reference_scores(
        CONFIG, model.make_weights(CONFIG, seed), tr.indices[:n],
        tr.mask[:n], tr.dense[:n], precision=precision, block=64)
    assert _digest(scores) == BEFORE[seed][precision]


def test_dlrm_head_flops_and_program_config():
    assert model.dense_flops_per_request(CONFIG) == 3904
    rc = model.head(CONFIG).program_config(CONFIG)
    assert (rc.arch, rc.embed_dim, rc.bottom_mlp, rc.mlp) == (
        "dlrm", 16, (32, 16), (32,))
    assert [(t.vocab, t.nnz) for t in rc.tables] == [(5000, 3), (300, 1),
                                                  (40, 1)]


TOY_HEAD = '''
"""A toy head: a linear score over the pooled bags and the dense
features."""
import functools

import jax
import jax.numpy as jnp

from chipbench import cells, model

KEYS = ("dense_gain",)


def check(cfg):
    if cfg["dense_gain"] <= 0:
        raise cells.SpecError("dense_gain must be positive")


def program_config(cfg):
    return None


def top_in(cfg):
    return cfg["n_dense"]


@functools.lru_cache(maxsize=None)
def _weights_fn(lines, n_dense):
    def make(k_emb, k_dense):
        table = jax.random.uniform(k_emb, (lines, model.LANES), jnp.float32)
        w = jax.random.uniform(k_dense, (n_dense, 1), jnp.float32)
        return {"emb": {"table": table}, "lin": {"w": w}}
    return jax.jit(make)


def make_weights(cfg, seed):
    lines = model.total_rows(cfg) * cfg["embed_dim"] // model.LANES
    return _weights_fn(lines, cfg["n_dense"])(
        model.base_key(seed), model.base_key(model.DENSE_SEED))


def reference_scores(cfg, weights, indices, mask, dense, precision, block):
    table, w = weights["emb"]["table"], weights["lin"]["w"]

    @jax.jit
    def fwd(rows, m, d):
        pooled = model.pool(table, rows, m, cfg["embed_dim"])
        return (jnp.sum(pooled, axis=(1, 2))
                + cfg["dense_gain"] * model.matmul(d, w, precision)[:, 0])
    return model.in_blocks(fwd, cfg, indices, mask, dense, block)


def dense_flops_per_request(cfg):
    return 2 * cfg["n_dense"]
'''


@pytest.fixture
def toy_root(tmp_path):
    """A root holding a toy head, its configuration, a mix and a
    BENCHMARK.json that names them, and nothing of the repository."""
    bench = tmp_path / "chipbench"
    for d in ("heads", "configs", "traffic"):
        (bench / d).mkdir(parents=True)
    (bench / "heads" / "toy.py").write_text(textwrap.dedent(TOY_HEAD))
    cfg = {k: v for k, v in json.loads((DATA / "tiny-config.json").read_text())
           .items() if k not in ("bottom_mlp", "top_mlp", "interaction_itself")}
    cfg.update(name="toy", arch="toy", dense_gain=0.5)
    (bench / "configs" / "toy.json").write_text(json.dumps(cfg))
    shutil.copy(DATA / "tiny-traffic.json", bench / "traffic" / "toy-mix.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "chipbench/configs/toy.json"}],
        "workloads": [{"name": "toy-cell", "config": "toy",
                       "traffic": "toy-mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "source": "host_clock"}],
        "per_layer": []}))
    return tmp_path


def test_head_from_files_only(toy_root):
    cell = cells.resolve("toy-cell", root=toy_root)
    cfg = cell.config
    assert cfg["arch"] == "toy" and cfg["dense_gain"] == 0.5
    # The repository's own heads do not hold it.
    with pytest.raises(cells.SpecError, match="has no head"):
        model.head(cfg)
    assert model.dense_flops_per_request(cfg, root=toy_root) == 26
    assert model.top_in(cfg, root=toy_root) == 13

    seed = 2**31 + 5
    w = model.make_weights(cfg, seed, root=toy_root)
    params = harness.host_params(cfg, w)
    table = params["emb"]["table"]
    assert table.shape == (model.total_rows(cfg), cfg["embed_dim"])
    assert params["lin"] is w["lin"]

    tr = traffic.make_traffic(seed, cfg, cell.traffic, 0.5)
    got = model.reference_scores(cfg, w, tr.indices, tr.mask, tr.dense,
                                 block=32, root=toy_root)
    rows = tr.indices + model.field_offsets(cfg)[None, :, None]
    pooled = np.where(tr.mask, table[rows].sum(axis=-1), 0.0).sum(axis=(1, 2))
    want = pooled + 0.5 * tr.dense @ np.asarray(w["lin"]["w"])[:, 0]
    assert len(got) == len(tr) > 32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_head_rules_run_on_files_only(toy_root):
    path = toy_root / "chipbench" / "configs" / "toy.json"
    cfg = json.loads(path.read_text())
    cfg["dense_gain"] = 0
    path.write_text(json.dumps(cfg))
    with pytest.raises(cells.SpecError, match="dense_gain must be positive"):
        cells.resolve("toy-cell", root=toy_root)
    del cfg["dense_gain"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(cells.SpecError, match="dense_gain"):
        cells.resolve("toy-cell", root=toy_root)
