"""Runtime layers: flow control, engine simulator, host service, serving
loop, elasticity, batcher, adaptive-cache controller."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adaptive_cache import (
    AdaptiveCacheController,
    EmaFrequencyTracker,
    MemoryModel,
    SlidingWindowLoadMonitor,
)
from repro.core.flow_control import compare_credit_paths
from repro.core.lookup_engine import HostLookupService
from repro.core.migration import ConnectionMigrator, plan_reshard
from repro.core.sharding import TableSpec, make_fused_tables
from repro.data import synthetic as syn
from repro.data.pipeline import BucketBatcher, PrefetchIterator
from repro.runtime.elastic import reshard_params
from repro.runtime.simulator import compare_engines, compare_migration


def _specs():
    return (
        TableSpec("a", 500, nnz=4),
        TableSpec("b", 300, nnz=2, pooling="mean"),
        TableSpec("c", 40, nnz=1),
    )


def _host_setup(rng, num_shards=4, pushdown=True, **kw):
    from repro.core.embedding import DisaggEmbedding

    specs = _specs()
    emb = DisaggEmbedding(specs=specs, dim=16, num_shards=num_shards)
    params = emb.init(jax.random.key(0))
    tables = make_fused_tables(specs, 16, num_shards)
    svc = HostLookupService(tables, np.asarray(params["table"]),
                            pushdown=pushdown, **kw)
    return emb, params, tables, svc


def test_host_service_matches_oracle(rng):
    emb, params, tables, svc = _host_setup(rng)
    try:
        b = syn.recsys_batch(rng, tables.specs, 16)
        ref = emb.lookup_reference(
            params, jnp.asarray(b["indices"]), jnp.asarray(b["mask"])
        )
        out = svc.lookup(b["indices"], b["mask"])
        np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-4, atol=1e-5)
    finally:
        svc.close()


def test_host_service_bag_id_broadcast(rng):
    """Regression for the bag-id layout in HostLookupService.lookup: every
    (b, f) pair owns exactly one bag id, contiguous in row-major order, and
    both pushdown modes pool identically under it (each bag's nnz entries
    must land in bag b*F+f — a broadcast bug would smear rows across bags).
    """
    emb, params, tables, svc_pd = _host_setup(rng)
    _, _, _, svc_raw = _host_setup(rng, pushdown=False)
    try:
        B, F, NNZ = 8, len(tables.specs), 4
        bag = np.broadcast_to(
            np.arange(B * F).reshape(B, F, 1), (B, F, NNZ)
        )
        assert bag.shape == (B, F, NNZ)
        # each bag id constant over its nnz axis, strictly increasing over (b,f)
        assert (bag == bag[:, :, :1]).all()
        np.testing.assert_array_equal(
            bag[:, :, 0].ravel(), np.arange(B * F)
        )
        b = syn.recsys_batch(rng, tables.specs, B)
        ref = emb.lookup_reference(
            params, jnp.asarray(b["indices"]), jnp.asarray(b["mask"])
        )
        for svc in (svc_pd, svc_raw):
            out = svc.lookup(b["indices"], b["mask"])
            assert out.shape == (B, F, 16)
            np.testing.assert_allclose(
                out, np.asarray(ref), rtol=1e-4, atol=1e-5
            )
    finally:
        svc_pd.close()
        svc_raw.close()


def test_simulator_reports_engine_utilization():
    from repro.runtime.simulator import LookupSimulator, SimConfig

    out = LookupSimulator(SimConfig(n_batches=200)).run()
    util = out["engine_utilization"]
    assert len(util) == SimConfig().n_engines
    assert all(0.0 <= u <= 1.0 for u in util)
    assert sum(out["engine_busy_s"]) > 0
    # a closed loop at inflight=8 keeps the engines meaningfully busy
    assert max(util) > 0.2


def test_pushdown_reduces_network_bytes(rng):
    """The paper's Fig-4 claim: hierarchical pooling moves fewer bytes for
    multi-hot bags than returning raw rows."""
    emb, params, tables, svc_pd = _host_setup(rng, pushdown=True)
    _, _, _, svc_raw = _host_setup(rng, pushdown=False)
    try:
        # many multi-hot hits per shard -> pushdown wins
        b = syn.recsys_batch(rng, tables.specs, 256)
        assert svc_pd.network_bytes(b["indices"], b["mask"]) < \
            svc_raw.network_bytes(b["indices"], b["mask"])
    finally:
        svc_pd.close()
        svc_raw.close()


def test_engine_simulator_matches_paper_regime():
    r = compare_engines(n_batches=300)
    assert 1.5 <= r["speedup"] <= 4.0, r  # paper: "up to 2.3x"


def test_migration_helps_under_skew():
    m = compare_migration(n_batches=300, n_units=8)
    assert m["speedup"] >= 0.95, m  # must not hurt; typically ~1.05-1.2x


def test_credit_priority_channel():
    r = compare_credit_paths(num_responses=256)
    reduction = 1 - r["flexemr"]["mean_credit_latency"] / r["strawman"]["mean_credit_latency"]
    assert reduction > 0.3, r  # paper: 35% lower credit latency


def test_connection_migrator_reassociates(rng):
    emb, params, tables, svc = _host_setup(rng, num_shards=8, num_engines=2)
    try:
        mig = ConnectionMigrator(svc, imbalance_threshold=0.5)
        b = syn.recsys_batch(rng, tables.specs, 64)
        # hammer one shard by restricting indices to its range
        svc.lookup(b["indices"], b["mask"])
        events = mig.rebalance_once()
        for ev in events:
            assert ev.reassociated
        # service still answers correctly after migration
        ref = emb.lookup_reference(params, jnp.asarray(b["indices"]), jnp.asarray(b["mask"]))
        out = svc.lookup(b["indices"], b["mask"])
        np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-4, atol=1e-5)
    finally:
        svc.close()


def test_plan_reshard_reduces_imbalance():
    tables = make_fused_tables(_specs(), 16, 8)
    load = np.array([8.0, 1, 1, 1, 1, 1, 1, 1])
    plan = plan_reshard(load, tables)
    assert plan.expected_imbalance_after < plan.expected_imbalance_before


def test_elastic_reshard_lossless(rng):
    from repro.core.embedding import DisaggEmbedding

    specs = _specs()
    emb4 = DisaggEmbedding(specs=specs, dim=16, num_shards=4)
    params = emb4.init(jax.random.key(1))
    new_tables, new_params = reshard_params(emb4.sharded, params["emb"] if "emb" in params else params, 8)
    emb8 = DisaggEmbedding(specs=specs, dim=16, num_shards=8)
    b = syn.recsys_batch(rng, specs, 8)
    ref = emb4.lookup_reference(params, jnp.asarray(b["indices"]), jnp.asarray(b["mask"]))
    out = emb8.lookup_reference(
        {"table": jnp.asarray(new_params["table"])},
        jnp.asarray(b["indices"]), jnp.asarray(b["mask"]),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- adaptive controller


def test_memory_model_tradeoff():
    mm = MemoryModel(fixed_bytes=1 << 30, bytes_per_sample=1 << 20, hbm_bytes=16 << 30)
    # bigger batch -> smaller cache budget (the Fig-7 contention)
    assert mm.cache_budget_bytes(1024) < mm.cache_budget_bytes(128)
    # bigger cache -> smaller max batch
    assert mm.max_batch_given_cache(8 << 30) < mm.max_batch_given_cache(1 << 30)


def test_controller_shrinks_under_load(rng):
    mm = MemoryModel(fixed_bytes=1 << 30, bytes_per_sample=1 << 21, hbm_bytes=16 << 30)
    ctl = AdaptiveCacheController(_specs(), 16, mm, field_replication=False,
                                  max_rows=10**9)
    for _ in range(8):
        ctl.observe(128, rng.integers(0, 800, 512))
    small_load = ctl.plan(128).capacity_rows
    for _ in range(64):
        ctl.observe(6000, rng.integers(0, 800, 512))
    high_load = ctl.plan(6000).capacity_rows
    assert high_load < small_load


def test_tracker_finds_hot_rows(rng):
    tr = EmaFrequencyTracker()
    hot = np.array([7, 13, 21])
    for _ in range(10):
        tr.update(np.concatenate([np.repeat(hot, 20), rng.integers(0, 1000, 40)]))
    top = set(tr.top_k(3).tolist())
    assert top == set(hot.tolist())
    assert tr.hot_fraction_covered(3) > 0.5


def test_sliding_window_monitor():
    mon = SlidingWindowLoadMonitor(window=4, high_frac=0.5)
    for b in (10, 10, 100, 100):
        mon.observe(b)
    assert mon.is_high_load(max_batch=110)
    assert not mon.is_high_load(max_batch=1000)


# ------------------------------------------------------------------ pipeline


def test_bucket_batcher_pads():
    b = BucketBatcher(buckets=(4, 8), max_wait=0.01)
    for i in range(5):
        b.submit({"x": np.full((2,), i, np.float32)})
    bucket, reqs = b.poll()
    assert bucket == 8 and len(reqs) == 5
    batch = b.pad_batch(reqs, bucket, {"x": ((2,), np.float32)})
    assert batch["x"].shape == (8, 2)
    assert batch["valid"].sum() == 5


def test_prefetch_iterator_restartable():
    it = PrefetchIterator(lambda step: {"step": step}, start_step=5, depth=1)
    first = next(it)
    assert first["step"] == 5
    assert it.state()["step"] == 6
    it.close()


def test_serve_launcher_cuts_rows_and_keeps_widths():
    from repro.configs import dlrm_flexemr
    from repro.launch import serve

    full = dlrm_flexemr.make_config()
    cut = serve.make_config("dlrm-flexemr", row_cut=8)
    assert [t.vocab for t in cut.tables] == [t.vocab // 8 for t in full.tables]
    assert (cut.embed_dim, cut.bottom_mlp, cut.mlp, cut.num_fields) == (
        full.embed_dim, full.bottom_mlp, full.mlp, full.num_fields)
    with pytest.raises(ValueError):
        serve.make_config("dlrm-flexemr", row_cut=0)


def test_serve_launcher_runs_dlrm_flexemr_widths():
    """`launch.serve` end to end at the paper model's widths (rows cut to a
    few thousand): every bucket's dense stage compiles before traffic and
    every request retires."""
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--config", "dlrm-flexemr", "--row-cut", "100000",
         "--requests", "96", "--num-engines", "2"]
    )
    out = serve.run(args)
    assert out["requests"] == 96
    assert sorted(out["warmup_s"]) == [32, 64, 128, 256, 512, 1024]
