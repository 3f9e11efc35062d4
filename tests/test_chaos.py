"""Chaos harness: fault injection + live elasticity under traffic.

The load-bearing contracts (mirroring benchmarks/chaos_bench.py):
  * bit-equality — retired scores under any fault schedule (engine-thread
    kill, shard drop with cache-tier re-replication, straggler storm, live
    reshard) are identical to a fault-free replay, at every pipeline depth
    and with wire dedup on or off;
  * zero hangs — a dropped shard parks cold-row WRs instead of hanging
    them, the watchdog force-restores an outage that outlives its batch,
    close() drains with faults still pending, and the pool settles
    leftover parked WRs at shutdown;
  * determinism — the firing sequence, the deterministic half of the
    ``chaos.`` summary, and SLO verdicts fed from virtual latencies are
    pure functions of the schedule's seed.

Also home to the reshard safety net: migration-plan validation (a
malformed plan must raise, not silently drop rows) and property tests for
the elastic N->M->N round trip.
"""
import threading

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.chaos import (
    FAULT_DROP_SHARD,
    FAULT_KILL_ENGINE,
    FAULT_KINDS,
    FAULT_RESHARD,
    FAULT_STRAGGLER_STORM,
    ChaosInjector,
    DegradedShard,
    FaultSchedule,
    FaultSpec,
)
from repro.core.adaptive_cache import AdaptiveCacheController, MemoryModel
from repro.core.lookup_engine import EmbeddingServer, ShardUnavailableError
from repro.core.migration import (
    ReshardPlan,
    apply_reshard,
    permutation,
    plan_reshard,
)
from repro.core.sharding import TableSpec, make_fused_tables
from repro.data import synthetic as syn
from repro.data.pipeline import BucketBatcher
from repro.models import recsys as R
from repro.obs.metrics import MetricsRegistry
from repro.obs.slo import SloMonitor, SloObjective
from repro.rdma import PooledLookupService
from repro.runtime.elastic import reshard_tables
from repro.runtime.serving import FlexEMRServer


# ----------------------------------------------------------- fault taxonomy


def test_fault_spec_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("power_cut", at_batch=1)


def test_fault_spec_requires_exactly_one_trigger():
    with pytest.raises(ValueError, match="exactly one"):
        FaultSpec(FAULT_KILL_ENGINE)  # neither
    with pytest.raises(ValueError, match="exactly one"):
        FaultSpec(FAULT_KILL_ENGINE, at_batch=1, at_vtime=0.5)  # both


def test_fault_spec_rejects_speedup_mult():
    with pytest.raises(ValueError, match="latency_mult"):
        FaultSpec(FAULT_STRAGGLER_STORM, at_batch=1, latency_mult=0.5)


def test_fault_schedule_generate_is_seed_deterministic():
    a = FaultSchedule.generate(7, num_batches=32, num_engines=4, num_shards=4)
    b = FaultSchedule.generate(7, num_batches=32, num_engines=4, num_shards=4)
    assert a == b
    assert len(a.faults) == 4
    trig = [f.at_batch for f in a.faults]
    assert trig == sorted(trig)
    assert all(1 <= t < 32 for t in trig)
    assert all(f.kind in FAULT_KINDS for f in a.faults)


def test_fault_schedule_generate_seeds_differ():
    schedules = {
        FaultSchedule.generate(s, num_batches=64, num_engines=4,
                               num_shards=4).faults
        for s in range(8)
    }
    assert len(schedules) > 1  # overwhelmingly: all 8 distinct


def test_fault_schedule_generate_rejects_tiny_run():
    with pytest.raises(ValueError, match="num_batches"):
        FaultSchedule.generate(0, num_batches=1, num_engines=4, num_shards=4)


# ----------------------------------------------------------- degraded shard


def _shard(rows=32, dim=8, start=0, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(rows, dim)).astype(np.float32)
    return EmbeddingServer(0, start, data), data


def test_degraded_shard_serves_replica_bit_equal():
    real, data = _shard()
    hot = np.array([3, 7, 11], np.int64)
    deg = DegradedShard(real, hot, data[hot].copy())
    assert deg.replica_rows == 3
    np.testing.assert_array_equal(deg.lookup_rows(hot), real.lookup_rows(hot))
    # pooled merge from the replica is the same f64 np.add.at as the real
    bag = np.array([0, 0, 1], np.int64)
    np.testing.assert_array_equal(
        deg.lookup_pooled(hot, bag, 2), real.lookup_pooled(hot, bag, 2)
    )
    assert deg.served_rows == 6 and deg.refused == 0


def test_degraded_shard_cold_row_fails_fast():
    real, data = _shard()
    deg = DegradedShard(real, np.array([3], np.int64), data[[3]].copy())
    with pytest.raises(ShardUnavailableError, match="row 4"):
        deg.lookup_rows(np.array([3, 4], np.int64))
    assert deg.refused == 1
    with pytest.raises(ShardUnavailableError):
        deg.read_range(0, 2)


def test_degraded_shard_restore_forwards_everything():
    real, data = _shard()
    deg = DegradedShard(real, np.zeros(0, np.int64),
                        np.zeros((0, 8), np.float32))
    with pytest.raises(ShardUnavailableError):
        deg.lookup_rows(np.array([5], np.int64))
    deg.restore()  # stale in-flight references now hit the real server
    np.testing.assert_array_equal(
        deg.lookup_rows(np.array([5], np.int64)), data[[5]]
    )
    np.testing.assert_array_equal(deg.read_range(2, 3), data[2:5])


# ------------------------------------------------- engine pool fault surface


def _pool_setup(num_shards=4, dim=16, num_threads=4, **kw):
    specs = (
        TableSpec("a", 500, nnz=4),
        TableSpec("b", 300, nnz=2, pooling="mean"),
        TableSpec("c", 40, nnz=1),
    )
    tables = make_fused_tables(specs, dim, num_shards)
    rng = np.random.default_rng(7)
    tnp = (0.05 * rng.normal(size=(tables.total_rows, dim))).astype(
        np.float32
    )
    return tables, tnp, PooledLookupService(
        tables, tnp, num_threads=num_threads, **kw
    )


def test_kill_thread_redeals_and_stays_bit_equal(rng):
    tables, tnp, svc = _pool_setup(num_threads=3)
    try:
        batches = [syn.recsys_batch(rng, tables.specs, 16) for _ in range(3)]
        ref = [svc.lookup(b["indices"], b["mask"]) for b in batches]
        svc.pool.kill_thread(1)
        assert svc.pool.alive_threads() == 2
        assert svc.pool.kill_thread(1) == 0  # already dead: no-op
        for b, r in zip(batches, ref):
            np.testing.assert_array_equal(svc.lookup(b["indices"], b["mask"]), r)
        svc.pool.kill_thread(0)
        with pytest.raises(ValueError, match="last alive"):
            svc.pool.kill_thread(2)
        # a single survivor still serves the full stream, bit-equal
        for b, r in zip(batches, ref):
            np.testing.assert_array_equal(svc.lookup(b["indices"], b["mask"]), r)
        s = svc.engine_summary()
        assert s["killed_threads"] == 2 and s["alive_threads"] == 1
    finally:
        svc.close()
    dead = [t for t in svc.pool.threads if t.dead]
    assert len(dead) == 2 and all(not t.is_alive() for t in svc.pool.threads)


def test_drop_shard_parks_cold_rows_until_restore(rng):
    tables, tnp, svc = _pool_setup()
    try:
        b = syn.recsys_batch(rng, tables.specs, 16)
        ref = svc.lookup(b["indices"], b["mask"])
        # drop shard 0 with an EMPTY replica: every shard-0 row is cold
        deg = DegradedShard(svc.pool.servers[0], np.zeros(0, np.int64),
                            np.zeros((0, tnp.shape[1]), np.float32))
        svc.pool.mark_shard_dropped(0, deg)
        assert svc.pool.dropped_shards() == [0]
        h = svc.lookup_async(b["indices"], b["mask"], hedge_timeout=None)
        with pytest.raises(TimeoutError):
            h.wait(0.3)  # blocked on parked WRs, NOT failed
        assert svc.pool.parked_count() > 0
        released = svc.pool.restore_shard(0)
        assert released > 0
        np.testing.assert_array_equal(h.wait(5.0), ref)
        assert svc.pool.parked_count() == 0
        s = svc.engine_summary()
        assert s["wrs_parked"] == s["parked_released"] == released
        assert s["dropped_shards"] == []
    finally:
        svc.close()


def test_pool_close_settles_parked_wrs(rng):
    tables, tnp, svc = _pool_setup()
    b = syn.recsys_batch(rng, tables.specs, 8)
    deg = DegradedShard(svc.pool.servers[0], np.zeros(0, np.int64),
                        np.zeros((0, tnp.shape[1]), np.float32))
    svc.pool.mark_shard_dropped(0, deg)
    h = svc.lookup_async(b["indices"], b["mask"], hedge_timeout=None)
    with pytest.raises(TimeoutError):
        h.wait(0.3)
    svc.close()  # backstop: parked WRs settle with the outage error
    with pytest.raises(ShardUnavailableError, match="still down"):
        h.wait(1.0)
    assert all(not t.is_alive() for t in svc.pool.threads)


def test_reshard_refused_while_shard_dropped():
    _, tnp, svc = _pool_setup()
    try:
        deg = DegradedShard(svc.pool.servers[0], np.zeros(0, np.int64),
                            np.zeros((0, tnp.shape[1]), np.float32))
        svc.pool.mark_shard_dropped(0, deg)
        with pytest.raises(RuntimeError, match="restore first"):
            svc.pool.set_servers(list(svc.pool.servers))
        svc.pool.restore_shard(0)
        svc.pool.set_servers(list(svc.pool.servers))  # now fine
    finally:
        svc.close()


def test_straggler_storm_prices_virtual_latency(rng):
    tables, _, svc = _pool_setup()
    try:
        b = syn.recsys_batch(rng, tables.specs, 32)
        ref = svc.lookup(b["indices"], b["mask"])
        base_span = svc.pool.virtual_span
        svc.pool.latency_mults[0] = 50.0
        out = svc.lookup(b["indices"], b["mask"])
        storm_span = svc.pool.virtual_span - base_span
        np.testing.assert_array_equal(out, ref)  # slower, never different
        assert storm_span > base_span  # the mult shows up on the v-clock
        svc.pool.latency_mults.clear()
        svc.lookup(b["indices"], b["mask"])
        assert svc.pool.virtual_span - (base_span + storm_span) < storm_span
    finally:
        svc.close()


# ----------------------------------------------- serving-level chaos matrix


def _tiny_cfg():
    tables = (
        TableSpec("big", 4000, nnz=4),
        TableSpec("mid", 1000, nnz=2),
        TableSpec("small", 64, nnz=1),
    )
    return R.RecsysConfig(
        name="chaos-t", arch="dlrm", tables=tables, embed_dim=16, n_dense=13,
        bottom_mlp=(64, 16), mlp=(64, 32),
    )


def _controller(cfg):
    return AdaptiveCacheController(
        cfg.tables, cfg.embed_dim,
        MemoryModel(fixed_bytes=1 << 20, bytes_per_sample=1 << 10,
                    hbm_bytes=1 << 28),
        field_replication=False, max_rows=1024,
    )


# The six-batch plan every scenario test replays: one fault of each kind,
# recoveries inside the run (drop restores at 5, storm at 6, reshard 4->8).
_SCENARIO = FaultSchedule(faults=(
    FaultSpec(FAULT_KILL_ENGINE, at_batch=2, target=1),
    FaultSpec(FAULT_DROP_SHARD, at_batch=3, target=0, duration_batches=2),
    FaultSpec(FAULT_STRAGGLER_STORM, at_batch=4, target=1,
              duration_batches=2, latency_mult=8.0),
    FaultSpec(FAULT_RESHARD, at_batch=5, target=8),
), seed=0)


def _serve_chaos(cfg, params, tables, reqs, depth, dedup, chaos=None,
                 registry=None, slo=None):
    """Explicit admit/retire drive (step()'s early-retire check is
    wall-racy; this keeps the batch clock deterministic)."""
    server = FlexEMRServer(
        cfg, params, tables, controller=_controller(cfg),
        cache_refresh_every=3, pipeline_depth=depth, hedge_timeout=0.05,
        # A poll window no busy machine outlasts: the queued requests always
        # cut full batches of 8, so the batch count replays exactly.
        dedup=dedup, batcher=BucketBatcher(buckets=(8,), max_wait=0.05),
        chaos=chaos, registry=registry or MetricsRegistry(), slo=slo,
    )
    try:
        for r in reqs:
            server.submit(r)
        outs = []
        while True:
            while len(server._pipeline) < server.pipeline_depth \
                    and server._admit_next():
                pass
            if not server._pipeline:
                break
            outs.append(server._retire_oldest()["scores"])
        vlat = list(server.service.virtual_latencies)
        engine = server.engine_summary()
    finally:
        server.close()
    return outs, vlat, engine


@pytest.fixture(scope="module")
def chaos_fixture():
    cfg = _tiny_cfg()
    params = R.init_params(cfg, jax.random.key(0))
    tables = make_fused_tables(cfg.tables, cfg.embed_dim, 4)
    rng = np.random.default_rng(3)
    reqs = []
    for _ in range(48):
        b = syn.recsys_batch(rng, cfg.tables, 1, n_dense=cfg.n_dense)
        reqs.append({"indices": b["indices"][0], "mask": b["mask"][0],
                     "dense": b["dense"][0]})
    refs = {
        dedup: _serve_chaos(cfg, params, tables, reqs, 1, dedup)[0]
        for dedup in (True, False)
    }
    assert len(refs[True]) == 6
    return cfg, params, tables, reqs, refs


@pytest.mark.parametrize("dedup", [True, False])
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_chaos_scores_bit_equal(chaos_fixture, depth, dedup):
    """The tentpole invariant: kill + drop + storm + reshard under live
    traffic change nothing about the retired scores — at every pipeline
    depth, with wire dedup on and off."""
    cfg, params, tables, reqs, refs = chaos_fixture
    injector = ChaosInjector(_SCENARIO, watchdog_s=10.0)
    outs, _, engine = _serve_chaos(
        cfg, params, tables, reqs, depth, dedup, chaos=injector
    )
    summ = injector.summary()
    assert summ["faults_fired"] == 4 and summ["faults_skipped"] == 0
    assert summ["by_kind"] == {k: 1 for k in FAULT_KINDS}
    assert summ["reshards"] == 1 and summ["moved_rows"] > 0
    assert summ["restores"] == 1 and summ["active_drops"] == []
    assert summ["wall"]["forced_restores"] == 0
    assert engine["killed_threads"] == 1 and engine["parked_now"] == 0
    assert len(outs) == len(refs[dedup])
    for i, (a, b) in enumerate(zip(outs, refs[dedup])):
        np.testing.assert_array_equal(a, b, err_msg=(
            f"depth={depth} dedup={dedup} batch={i} diverged under chaos"
        ))


def test_chaos_drain_on_close_with_fault_pending(chaos_fixture):
    """close() with a shard still down and the pipeline full: drain()
    restores the outage first, every admitted batch completes, the engine
    threads exit — no hang, no leaked parked WRs."""
    cfg, params, tables, reqs, _ = chaos_fixture
    schedule = FaultSchedule(faults=(
        FaultSpec(FAULT_DROP_SHARD, at_batch=1, target=0),  # indefinite
    ), seed=0)
    injector = ChaosInjector(schedule, watchdog_s=10.0)
    server = FlexEMRServer(
        cfg, params, tables, controller=_controller(cfg),
        cache_refresh_every=3, pipeline_depth=4, hedge_timeout=None,
        batcher=BucketBatcher(buckets=(8,), max_wait=0.001),
        chaos=injector, registry=MetricsRegistry(),
    )
    for r in reqs[:32]:
        server.submit(r)
    while len(server._pipeline) < 4 and server._admit_next():
        pass
    assert injector.summary()["active_drops"] == [0]
    server.close()
    assert not server._pipeline
    assert injector.summary()["active_drops"] == []
    assert server.service.pool.parked_count() == 0
    assert all(not t.is_alive() for t in server.service.pool.threads)


def test_chaos_watchdog_force_restores_indefinite_drop(chaos_fixture):
    """An outage with no scheduled recovery outlives its batch: the
    guarded wait's watchdog force-restores it instead of hanging — and
    the scores STILL match the fault-free run."""
    cfg, params, tables, reqs, refs = chaos_fixture
    schedule = FaultSchedule(faults=(
        FaultSpec(FAULT_DROP_SHARD, at_batch=2, target=0),  # indefinite
    ), seed=0)
    injector = ChaosInjector(schedule, watchdog_s=0.4, wait_step_s=0.1)
    outs, _, engine = _serve_chaos(
        cfg, params, tables, reqs, 2, True, chaos=injector
    )
    summ = injector.summary()
    assert summ["wall"]["forced_restores"] >= 1
    assert summ["restores"] == 1 and summ["active_drops"] == []
    assert engine["parked_now"] == 0
    for a, b in zip(outs, refs[True]):
        np.testing.assert_array_equal(a, b)


def test_chaos_registers_metrics_namespace(chaos_fixture):
    """chaos.* lands in the unified registry snapshot next to serve.*."""
    cfg, params, tables, reqs, _ = chaos_fixture
    registry = MetricsRegistry()
    injector = ChaosInjector(_SCENARIO, watchdog_s=10.0)
    _serve_chaos(cfg, params, tables, reqs, 2, True, chaos=injector,
                 registry=registry)
    snap = registry.snapshot()
    assert snap["chaos.faults_fired"] == 4
    assert snap["chaos.restores"] == 1
    assert any(k.startswith("serve.") for k in snap)


def test_chaos_requires_pooled_engine(chaos_fixture):
    cfg, params, tables, _, _ = chaos_fixture
    with pytest.raises(ValueError, match="pooled"):
        FlexEMRServer(
            cfg, params, tables, engine="legacy",
            chaos=ChaosInjector(_SCENARIO), registry=MetricsRegistry(),
        )


# ---------------------------------------------------------------- determinism


def _strip_wall(summary: dict) -> dict:
    return {k: v for k, v in summary.items() if k != "wall"}


def test_chaos_same_seed_same_firing_and_summary(chaos_fixture):
    """Two runs of the same schedule: identical firing log, identical
    deterministic summary, identical scores and virtual latencies.  The
    wall sub-dict is exactly the racy remainder and is NOT compared."""
    cfg, params, tables, reqs, _ = chaos_fixture
    runs = []
    for _ in range(2):
        injector = ChaosInjector(_SCENARIO, watchdog_s=10.0)
        outs, vlat, _ = _serve_chaos(
            cfg, params, tables, reqs, 2, True, chaos=injector
        )
        runs.append((outs, vlat, injector.summary()))
    (outs_a, vlat_a, summ_a), (outs_b, vlat_b, summ_b) = runs
    assert summ_a["firing_log"] == summ_b["firing_log"]
    assert [k for (_, k, _) in summ_a["firing_log"]] == list(FAULT_KINDS)
    assert _strip_wall(summ_a) == _strip_wall(summ_b)
    assert vlat_a == vlat_b  # virtual timeline is seed-stable too
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(a, b)


def test_chaos_slo_verdicts_deterministic(chaos_fixture):
    """SLO monitors fed the virtual latency stream (explicit now) reach
    bit-identical verdicts across replays of the same chaos seed."""
    cfg, params, tables, reqs, _ = chaos_fixture
    summaries = []
    for _ in range(2):
        injector = ChaosInjector(_SCENARIO, watchdog_s=10.0)
        _, vlat, _ = _serve_chaos(
            cfg, params, tables, reqs, 2, True, chaos=injector
        )
        mon = SloMonitor(SloObjective(
            latency_target_s=float(np.median(vlat)), target=0.5,
            min_samples=2,
        ))
        now = 0.0
        for lat in vlat:
            now += lat
            mon.observe(lat, now=now)
        summaries.append(mon.summary(now=now))
    assert summaries[0] == summaries[1]


def test_chaos_generated_schedules_replay_identically():
    """FaultSchedule.generate feeds the injector exactly as hand-written
    plans do; two injectors over the same generated schedule agree."""
    sched = FaultSchedule.generate(11, num_batches=6, num_engines=4,
                                   num_shards=4)
    cfg = _tiny_cfg()
    params = R.init_params(cfg, jax.random.key(0))
    tables = make_fused_tables(cfg.tables, cfg.embed_dim, 4)
    rng = np.random.default_rng(5)
    reqs = []
    for _ in range(24):
        b = syn.recsys_batch(rng, cfg.tables, 1, n_dense=cfg.n_dense)
        reqs.append({"indices": b["indices"][0], "mask": b["mask"][0],
                     "dense": b["dense"][0]})
    ref, _, _ = _serve_chaos(cfg, params, tables, reqs, 2, True)
    logs = []
    for _ in range(2):
        injector = ChaosInjector(sched, watchdog_s=10.0)
        outs, _, _ = _serve_chaos(
            cfg, params, tables, reqs, 2, True, chaos=injector
        )
        for a, b in zip(outs, ref):
            np.testing.assert_array_equal(a, b)
        logs.append(injector.summary()["firing_log"])
    assert logs[0] == logs[1]


# ------------------------------------------------- reshard plans + elasticity


def _plan_tables(num_shards=4, rows_per_shard=8):
    return make_fused_tables(
        (TableSpec("t", num_shards * rows_per_shard, nnz=1),), 4, num_shards
    )


def test_permutation_rejects_wrong_boundary_count():
    tables = _plan_tables()
    plan = ReshardPlan(np.array([0, tables.total_rows]), 1.0, 1.0)
    with pytest.raises(ValueError, match="ranges for 4 shards"):
        permutation(plan, tables)


def test_permutation_rejects_partial_cover():
    tables = _plan_tables()
    n = tables.total_rows
    plan = ReshardPlan(np.array([0, 8, 16, 24, n - 1]), 1.0, 1.0)
    with pytest.raises(ValueError, match="covers"):
        permutation(plan, tables)
    plan = ReshardPlan(np.array([1, 8, 16, 24, n]), 1.0, 1.0)
    with pytest.raises(ValueError, match="covers"):
        permutation(plan, tables)


def test_permutation_rejects_decreasing_boundaries():
    tables = _plan_tables()
    n = tables.total_rows
    plan = ReshardPlan(np.array([0, 16, 8, 24, n]), 1.0, 1.0)
    with pytest.raises(ValueError, match="non-decreasing"):
        permutation(plan, tables)


def test_apply_reshard_rejects_wrong_table_length():
    tables = _plan_tables()
    n = tables.total_rows
    plan = ReshardPlan(np.array([0, 8, 16, 24, n]), 1.0, 1.0)
    with pytest.raises(ValueError, match="rows"):
        apply_reshard(np.zeros((n - 1, 4), np.float32), plan, tables)


def test_apply_reshard_valid_plan_preserves_rows(rng):
    tables = _plan_tables()
    n = tables.total_rows
    table = rng.normal(size=(n, 4)).astype(np.float32)
    plan = ReshardPlan(np.array([0, 4, 20, 28, n]), 1.0, 1.0)
    out = apply_reshard(table, plan, tables)
    assert out.shape == table.shape
    # a permutation: every original row survives exactly once
    np.testing.assert_array_equal(
        np.sort(out, axis=0), np.sort(table, axis=0)
    )


@settings(max_examples=15, deadline=None)
@given(
    vocab=st.integers(min_value=5, max_value=600),
    n_shards=st.integers(min_value=1, max_value=8),
    m_shards=st.integers(min_value=1, max_value=8),
)
def test_reshard_roundtrip_bit_exact(vocab, n_shards, m_shards):
    """Property (satellite): N -> M -> N resharding returns every raw row
    bit-exactly, for arbitrary vocab/shard-count combinations."""
    tables = make_fused_tables((TableSpec("t", vocab, nnz=1),), 4, n_shards)
    rng = np.random.default_rng(vocab * 64 + n_shards * 8 + m_shards)
    table = rng.normal(size=(tables.total_rows, 4)).astype(np.float32)
    mid = reshard_tables(tables, table, m_shards)
    back = reshard_tables(mid.tables, mid.table, n_shards)
    assert back.tables.total_rows == tables.total_rows
    raw = tables.raw_rows
    np.testing.assert_array_equal(back.table[:raw], table[:raw])
    # ownership-change count is symmetric and bounded by the raw rows
    assert 0 <= mid.moved_rows <= raw
    if n_shards == m_shards:
        assert mid.moved_rows == 0


@settings(max_examples=15, deadline=None)
@given(
    hot_shard=st.integers(min_value=0, max_value=7),
    hot_load=st.floats(min_value=2.0, max_value=64.0),
)
def test_plan_reshard_never_worsens_imbalance(hot_shard, hot_load):
    """Property (satellite): the rebalance plan's expected imbalance never
    exceeds the measured one, however the skew is shaped."""
    tables = _plan_tables(num_shards=8, rows_per_shard=16)
    load = np.ones(8)
    load[hot_shard] = hot_load
    plan = plan_reshard(load, tables)
    assert plan.expected_imbalance_after <= plan.expected_imbalance_before + 1e-9
    permutation(plan, tables)  # and the plan is always well-formed


def test_live_reshard_grow_shrink_under_traffic(chaos_fixture):
    """FlexEMRServer.reshard mid-stream (4 -> 8 -> 2) keeps scores
    bit-equal and reports moved rows + invalidated in-flight entries."""
    cfg, params, tables, reqs, refs = chaos_fixture
    server = FlexEMRServer(
        cfg, params, tables, controller=_controller(cfg),
        cache_refresh_every=3, pipeline_depth=2, hedge_timeout=0.05,
        batcher=BucketBatcher(buckets=(8,), max_wait=0.05),  # full batches
        registry=MetricsRegistry(),
    )
    try:
        for r in reqs:
            server.submit(r)
        outs = []
        cut = {2: 8, 4: 2}  # retire count -> new shard total
        while True:
            while len(server._pipeline) < server.pipeline_depth \
                    and server._admit_next():
                pass
            if not server._pipeline:
                break
            outs.append(server._retire_oldest()["scores"])
            if len(outs) in cut:
                res = server.reshard(cut[len(outs)])
                assert res["num_shards"] == cut[len(outs)]
                assert res["moved_rows"] > 0
        assert server.tables.num_shards == 2
        assert len(server.service.pool.servers) == 2
    finally:
        server.close()
    for a, b in zip(outs, refs[True]):
        np.testing.assert_array_equal(a, b)


def test_live_reshard_requires_new_shard_count(chaos_fixture):
    cfg, params, tables, _, _ = chaos_fixture
    server = FlexEMRServer(
        cfg, params, tables, registry=MetricsRegistry(),
        batcher=BucketBatcher(buckets=(8,), max_wait=0.001),
    )
    try:
        with pytest.raises(ValueError):
            server.reshard(0)
    finally:
        server.close()


def test_concurrent_traffic_during_restore(chaos_fixture):
    """Restore races a live submitter: lookups issued while the shard
    comes back still merge bit-equal (the park/retry path re-resolves)."""
    cfg, params, tables, _, _ = chaos_fixture
    rng = np.random.default_rng(9)
    batches = [syn.recsys_batch(rng, tables.specs, 8) for _ in range(6)]
    svc = PooledLookupService(tables, np.asarray(params["emb"]["table"]),
                              num_threads=4)
    try:
        ref = [svc.lookup(b["indices"], b["mask"]) for b in batches]
        deg = DegradedShard(svc.pool.servers[0], np.zeros(0, np.int64),
                            np.zeros((0, cfg.embed_dim), np.float32))
        svc.pool.mark_shard_dropped(0, deg)
        handles = [
            svc.lookup_async(b["indices"], b["mask"], hedge_timeout=None)
            for b in batches
        ]
        t = threading.Timer(0.2, lambda: (deg.restore(),
                                          svc.pool.restore_shard(0)))
        t.start()
        try:
            outs = [h.wait(10.0) for h in handles]
        finally:
            t.join()
        for a, b in zip(outs, ref):
            np.testing.assert_array_equal(a, b)
    finally:
        svc.close()
