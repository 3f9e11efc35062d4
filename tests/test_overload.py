"""SLO-aware overload control: deadline admission, the WR retry/backoff
ladder, and brownout degradation.

The load-bearing contracts (mirroring benchmarks/overload_bench.py):
  * admission — already-expired deadlines fast-fail at submit, the submit
    queue is bounded, the warmed-up estimator sheds unmeetable deadlines,
    and the effective pipeline depth shrinks under a sustained burn-rate
    alert and regrows on calm;
  * retry ladder — transient WR failures re-fly after seeded-deterministic
    exponential backoff, bounded by max_attempts AND a shared retry budget
    (a fraction of primary traffic); with no fault fired the ladder never
    engages and outputs are bit-equal with the policy off;
  * brownout — under ``degrade_policy="degrade"`` a dropped shard's cold
    rows answer as the cache tier's best partial (zero for truly absent)
    with per-request flags covering every diverging output; ``block``
    fails fast; ``strict`` keeps the PR-8 park-until-restore default;
  * composition — a straggler storm under 1.2x open-loop load with the
    retry budget on fires deterministically and yields identical SLO
    verdicts at every pipeline depth, with zero hangs and no leaked
    engine threads.
"""
import time

import jax
import numpy as np
import pytest

from repro.chaos import ChaosInjector, DegradedShard, FaultSchedule, FaultSpec
from repro.core.lookup_engine import ShardUnavailableError
from repro.core.sharding import TableSpec, make_fused_tables
from repro.data import synthetic as syn
from repro.data.pipeline import BucketBatcher
from repro.loadgen import (
    OpenLoopDriver,
    OpenLoopGenerator,
    RecsysPayloadFactory,
    constant,
)
from repro.models import recsys as R
from repro.obs.metrics import MetricsRegistry
from repro.obs.slo import SloMonitor, SloObjective
from repro.rdma import PooledLookupService
from repro.rdma.verbs import RetryPolicy, TransientWireError, VerbsTiming
from repro.runtime.admission import AdmissionController, ShedError
from repro.runtime.serving import FlexEMRServer


@pytest.fixture
def rng():
    return np.random.default_rng(11)


# ------------------------------------------------------ admission controller


def test_expired_deadline_sheds_before_warmup():
    adm = AdmissionController()
    with pytest.raises(ShedError) as ei:
        adm.check(now=10.0, arrival=9.0, deadline_s=0.5, queued=0,
                  occupancy=0)
    assert ei.value.reason == "expired"
    assert adm.shed_expired == 1 and adm.admitted == 0


def test_bounded_queue_sheds_at_capacity():
    adm = AdmissionController(max_queue=4)
    with pytest.raises(ShedError) as ei:
        adm.check(now=0.0, arrival=0.0, deadline_s=None, queued=4,
                  occupancy=0)
    assert ei.value.reason == "queue_full"
    # Below capacity, a deadline-less request always admits.
    adm.check(now=0.0, arrival=0.0, deadline_s=None, queued=3, occupancy=0)
    assert adm.admitted == 1 and adm.shed_queue_full == 1


def test_deadline_estimate_sheds_after_warmup():
    adm = AdmissionController(min_samples=4, headroom=1.0)
    assert adm.estimate_retire_s(0, 0) is None  # cold model never sheds
    now = 0.0
    for _ in range(6):  # 10ms per 8-request batch
        now += 0.010
        adm.on_retire(now, batch_size=8, alerting=False)
    est = adm.estimate_retire_s(queued=16, occupancy=2)
    # 16/8 queued batches + 2 occupied + own batch = 5 batches x ~10ms.
    assert est == pytest.approx(0.050, rel=0.2)
    with pytest.raises(ShedError) as ei:
        adm.check(now=now, arrival=now, deadline_s=0.5 * est, queued=16,
                  occupancy=2)
    assert ei.value.reason == "deadline"
    adm.check(now=now, arrival=now, deadline_s=10.0, queued=16, occupancy=2)
    assert adm.admitted == 1 and adm.shed_deadline == 1


def test_adaptive_depth_shrinks_and_regrows():
    adm = AdmissionController(min_depth=1, regrow_after=3)
    adm.attach(pipeline_depth=3)
    assert adm.depth == adm.max_depth == 3
    # Sustained alert: one step down per retire, floored at min_depth.
    deltas = [adm.on_retire(float(i), 8, alerting=True) for i in range(4)]
    assert deltas == [-1, -1, 0, 0] and adm.depth == 1
    # Calm retires regrow one step per regrow_after, ceilinged at max.
    deltas = [adm.on_retire(4.0 + i, 8, alerting=False) for i in range(7)]
    assert deltas.count(+1) == 2 and adm.depth == 3
    s = adm.summary()
    assert s["depth_shrinks"] == 2 and s["depth_regrows"] == 2


def test_admission_constructor_validation():
    with pytest.raises(ValueError):
        AdmissionController(max_queue=0)
    with pytest.raises(ValueError):
        AdmissionController(headroom=0.9)
    with pytest.raises(ValueError):
        AdmissionController(min_depth=0)


# ------------------------------------------------------ serving-level gating


def _tiny_cfg():
    tables = (
        TableSpec("big", 4000, nnz=4),
        TableSpec("mid", 1000, nnz=2),
        TableSpec("small", 64, nnz=1),
    )
    return R.RecsysConfig(
        name="overload-t", arch="dlrm", tables=tables, embed_dim=16,
        n_dense=13, bottom_mlp=(64, 16), mlp=(64, 32),
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_cfg()
    params = R.init_params(cfg, jax.random.key(0))
    tables = make_fused_tables(cfg.tables, cfg.embed_dim, 4)
    return cfg, params, tables


def _payload(rng, cfg):
    b = syn.recsys_batch(rng, cfg.tables, 1, n_dense=cfg.n_dense)
    return {"indices": b["indices"][0], "mask": b["mask"][0],
            "dense": b["dense"][0]}


def test_submit_expired_deadline_fast_fails(tiny, rng):
    cfg, params, tables = tiny
    registry = MetricsRegistry()
    server = FlexEMRServer(
        cfg, params, tables, pipeline_depth=2,
        batcher=BucketBatcher(buckets=(8,), max_wait=0.001),
        admission=AdmissionController(), registry=registry,
    )
    try:
        with pytest.raises(ShedError) as ei:
            server.submit(_payload(rng, cfg),
                          arrival=time.perf_counter() - 1.0, deadline_s=0.5)
        assert ei.value.reason == "expired"
        snap = registry.snapshot()
        assert snap["serve.admission.shed_expired"] == 1
        assert snap["serve.admission.admitted"] == 0
        assert snap["serve.admission.queue_depth"] == 0
    finally:
        server.close()


def test_submit_queue_full_sheds(tiny, rng):
    cfg, params, tables = tiny
    server = FlexEMRServer(
        cfg, params, tables, pipeline_depth=2,
        batcher=BucketBatcher(buckets=(8,), max_wait=0.001),
        admission=AdmissionController(max_queue=2),
    )
    try:
        server.submit(_payload(rng, cfg))
        server.submit(_payload(rng, cfg))
        with pytest.raises(ShedError) as ei:
            server.submit(_payload(rng, cfg))
        assert ei.value.reason == "queue_full"
        assert server.admission.shed_queue_full == 1
    finally:
        server.close()


def test_effective_depth_tracks_admission(tiny):
    cfg, params, tables = tiny
    adm = AdmissionController()
    server = FlexEMRServer(
        cfg, params, tables, pipeline_depth=4,
        batcher=BucketBatcher(buckets=(8,), max_wait=0.001), admission=adm,
    )
    try:
        assert adm.max_depth == 4 and server.effective_depth == 4
        adm.depth = 2  # what a sustained alert would do via on_retire
        assert server.effective_depth == 2
    finally:
        server.close()
    # Without admission the configured depth is the effective depth.
    server = FlexEMRServer(
        cfg, params, tables, pipeline_depth=3,
        batcher=BucketBatcher(buckets=(8,), max_wait=0.001),
    )
    try:
        assert server.effective_depth == 3
    finally:
        server.close()


def test_degrade_policy_requires_pooled_engine(tiny):
    cfg, params, tables = tiny
    with pytest.raises(ValueError, match="pooled"):
        FlexEMRServer(cfg, params, tables, engine="legacy",
                      degrade_policy="degrade")
    with pytest.raises(ValueError, match="degrade_policy"):
        FlexEMRServer(cfg, params, tables, degrade_policy="bogus")


# ------------------------------------------------------------- retry ladder


class _FlakyServer:
    """Wraps an EmbeddingServer; the first ``fail_first`` gathers raise
    TransientWireError, then it delegates cleanly."""

    def __init__(self, inner, fail_first: int):
        self._inner = inner
        self.failures_left = fail_first
        self.raised = 0

    def _maybe_fail(self):
        if self.failures_left > 0:
            self.failures_left -= 1
            self.raised += 1
            raise TransientWireError("injected flaky completion")

    def lookup_rows(self, row_ids):
        self._maybe_fail()
        return self._inner.lookup_rows(row_ids)

    def read_range(self, start, n):
        self._maybe_fail()
        return self._inner.read_range(start, n)

    def lookup_pooled(self, row_ids, bag_ids, num_bags):
        self._maybe_fail()
        return self._inner.lookup_pooled(row_ids, bag_ids, num_bags)

    def pool_segments(self, row_ids, seg_bounds):
        self._maybe_fail()
        return self._inner.pool_segments(row_ids, seg_bounds)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _pool_setup(num_shards=4, dim=16, **kw):
    specs = (
        TableSpec("a", 500, nnz=4),
        TableSpec("b", 300, nnz=2, pooling="mean"),
        TableSpec("c", 40, nnz=1),
    )
    tables = make_fused_tables(specs, dim, num_shards)
    prng = np.random.default_rng(7)
    tnp = (0.05 * prng.normal(size=(tables.total_rows, dim))).astype(
        np.float32
    )
    return tables, tnp, PooledLookupService(tables, tnp, **kw)


def test_retry_policy_backoff_is_seeded_deterministic():
    p = RetryPolicy(seed=3)
    a = [p.backoff_delay_s(0, 5, k) for k in (1, 2, 3)]
    b = [p.backoff_delay_s(0, 5, k) for k in (1, 2, 3)]
    assert a == b  # same (seed, server, slot, attempt) -> same delay
    assert a[0] < a[1] < a[2]  # exponential growth dominates the jitter
    assert p.backoff_delay_s(1, 5, 1) != a[0]  # server decorrelates


def test_transient_failures_retry_to_bit_equal(rng):
    tables, _, ref_svc = _pool_setup()
    b = syn.recsys_batch(rng, tables.specs, 16)
    try:
        ref = ref_svc.lookup(b["indices"], b["mask"])
    finally:
        ref_svc.close()
    outs, attempts = [], []
    for _ in range(2):
        _, _, svc = _pool_setup(
            retry_policy=RetryPolicy(budget_frac=0.5, seed=0)
        )
        try:
            svc.lookup(b["indices"], b["mask"])  # primaries fund the budget
            flaky = _FlakyServer(svc.pool.servers[0], fail_first=2)
            svc.pool.set_servers(
                [flaky] + list(svc.pool.servers[1:])
            )
            outs.append(svc.lookup(b["indices"], b["mask"]))
            summ = svc.retry_summary()
            attempts.append(summ["attempts"])
            assert flaky.raised == 2 and summ["attempts"] >= 2
            assert summ["charged"] >= 2 and summ["enabled"]
        finally:
            svc.close()
    np.testing.assert_array_equal(outs[0], ref)  # retried, never wrong
    np.testing.assert_array_equal(outs[1], ref)
    assert attempts[0] == attempts[1]  # the ladder replays identically


def test_retry_budget_exhausted_fails_loudly(rng):
    tables, _, svc = _pool_setup(retry_policy=RetryPolicy(budget_frac=0.0))
    try:
        flaky = _FlakyServer(svc.pool.servers[0], fail_first=10_000)
        svc.pool.set_servers([flaky] + list(svc.pool.servers[1:]))
        b = syn.recsys_batch(rng, tables.specs, 8)
        with pytest.raises(TransientWireError):
            svc.lookup(b["indices"], b["mask"])
        summ = svc.retry_summary()
        assert summ["budget"] == 0 and summ["denied"] >= 1
        assert summ["attempts"] == 0  # nothing flown past the budget
    finally:
        svc.close()


def test_no_policy_means_no_ladder(rng):
    tables, _, svc = _pool_setup()  # retry_policy=None
    try:
        flaky = _FlakyServer(svc.pool.servers[0], fail_first=1)
        svc.pool.set_servers([flaky] + list(svc.pool.servers[1:]))
        b = syn.recsys_batch(rng, tables.specs, 8)
        with pytest.raises(TransientWireError):
            svc.lookup(b["indices"], b["mask"])
        summ = svc.retry_summary()
        assert not summ["enabled"] and summ["attempts"] == 0
        assert summ["charged"] == 0
    finally:
        svc.close()


def test_policy_on_is_bit_equal_without_faults(rng):
    """The acceptance invariant: retries off vs on differ by zero bits
    when no fault fires, and the budget is never touched."""
    tables, _, plain = _pool_setup()
    b = syn.recsys_batch(rng, tables.specs, 32)
    try:
        ref = plain.lookup(b["indices"], b["mask"])
    finally:
        plain.close()
    _, _, svc = _pool_setup(retry_policy=RetryPolicy(budget_frac=0.25))
    try:
        np.testing.assert_array_equal(svc.lookup(b["indices"], b["mask"]), ref)
        summ = svc.retry_summary()
        assert summ["charged"] == summ["attempts"] == summ["timeouts"] == 0
    finally:
        svc.close()


# ---------------------------------------------------------------- brownout


def test_degrade_answers_partial_with_flags(rng):
    tables, tnp, svc = _pool_setup(degrade_policy="degrade")
    try:
        b = syn.recsys_batch(rng, tables.specs, 16)
        ref = svc.lookup(b["indices"], b["mask"])
        # Empty replica: every shard-0 row is cold -> zero-filled partial.
        deg = DegradedShard(svc.pool.servers[0], np.zeros(0, np.int64),
                            np.zeros((0, tnp.shape[1]), np.float32))
        svc.pool.mark_shard_dropped(0, deg)
        h = svc.lookup_async(b["indices"], b["mask"], hedge_timeout=None)
        out = h.wait(5.0)  # settles NOW — degrade never parks
        assert svc.pool.parked_count() == 0
        assert h.degraded_rows > 0 and len(h.degraded_bags) > 0
        # Divergence is confined to the flagged bags; everything else is
        # bit-equal to the healthy run.
        nb, F = out.shape[0], out.shape[1]
        flat_ref = ref.reshape(nb * F, -1)
        flat_out = out.reshape(nb * F, -1)
        moved = {
            i for i in range(nb * F)
            if not np.array_equal(flat_ref[i], flat_out[i])
        }
        assert moved  # the drop actually touched served bags
        assert moved <= h.degraded_bags
        s = svc.pool.summary()
        assert s["degraded_wrs"] > 0 and s["degraded_rows"] > 0
        assert s["degrade_policy"] == "degrade"
        svc.pool.restore_shard(0)
        np.testing.assert_array_equal(
            svc.lookup(b["indices"], b["mask"]), ref
        )
    finally:
        svc.close()


def test_block_policy_fails_fast_without_parking(rng):
    tables, tnp, svc = _pool_setup(degrade_policy="block")
    try:
        b = syn.recsys_batch(rng, tables.specs, 8)
        deg = DegradedShard(svc.pool.servers[0], np.zeros(0, np.int64),
                            np.zeros((0, tnp.shape[1]), np.float32))
        svc.pool.mark_shard_dropped(0, deg)
        t0 = time.perf_counter()
        with pytest.raises(ShardUnavailableError):
            svc.lookup(b["indices"], b["mask"])
        assert time.perf_counter() - t0 < 2.0  # failed, not parked
        assert svc.pool.parked_count() == 0
        svc.pool.restore_shard(0)
    finally:
        svc.close()


def test_server_close_reraises_inflight_failure(tiny, rng):
    """A batch that fails while close() drains it does not stop the drain
    or leak engine threads, and close() re-raises it afterwards."""
    cfg, params, tables = tiny
    server = FlexEMRServer(
        cfg, params, tables, pipeline_depth=2, hedge_timeout=None,
        batcher=BucketBatcher(buckets=(16,), max_wait=0.005),
        degrade_policy="block",
    )
    pool = server.service.pool
    pool.mark_shard_dropped(0, DegradedShard(
        pool.servers[0], np.zeros(0, np.int64),
        np.zeros((0, cfg.embed_dim), np.float32),
    ))
    for _ in range(32):
        server.submit(_payload(rng, cfg))
    while len(server._pipeline) < 2 and server._admit_next():
        pass
    assert len(server._pipeline) == 2
    with pytest.raises(ShardUnavailableError):
        server.close()
    assert not server._pipeline
    assert all(not t.is_alive() for t in pool.threads)
    server.close()  # idempotent: nothing left to drain or re-raise


def test_degrade_policy_validated():
    with pytest.raises(ValueError, match="degrade_policy"):
        _pool_setup(degrade_policy="nope")


def test_serving_degrade_flags_cover_all_divergence(tiny, rng):
    """Serving-level brownout: with a shard dropped mid-stream under
    ``degrade``, every request whose scores moved vs the fault-free run
    carries the ``degraded`` flag."""
    cfg, params, tables = tiny
    reqs = [_payload(rng, cfg) for _ in range(12 * 16)]

    def serve(policy, chaos=None):
        server = FlexEMRServer(
            cfg, params, tables, pipeline_depth=2, hedge_timeout=0.05,
            batcher=BucketBatcher(buckets=(16,), max_wait=0.005),
            degrade_policy=policy, chaos=chaos,
        )
        try:
            for r in reqs:
                server.submit(r)
            scores, flags = [], []
            while True:
                while len(server._pipeline) < server.pipeline_depth \
                        and server._admit_next():
                    pass
                if not server._pipeline:
                    break
                out = server._retire_oldest()
                n = len(out["degraded"])
                scores.append(np.asarray(out["scores"])[:n])
                flags.extend(out["degraded"])
            summary = server._degraded_summary()
        finally:
            server.close()
        return np.concatenate(scores), flags, summary

    ref, ref_flags, _ = serve("strict")
    assert not any(ref_flags)
    sched = FaultSchedule(faults=(
        FaultSpec("drop_shard", at_batch=4, target=0, duration_batches=2),
    ), seed=0)
    out, flags, summary = serve(
        "degrade", chaos=ChaosInjector(sched, watchdog_s=10.0)
    )
    assert out.shape == ref.shape and len(flags) == len(reqs)
    moved = [i for i in range(len(flags))
             if not np.array_equal(ref[i], out[i])]
    assert all(flags[i] for i in moved)  # flags cover every divergence
    assert summary["requests"] == sum(flags)
    assert summary["policy"] == "degrade"


# ------------------------------------------- chaos x overload composition


def test_storm_under_overload_identical_across_depths(tiny):
    """The satellite composition: a straggler storm under ~1.2x open-loop
    load with the retry budget on.  Across pipeline depths {1,2,4}: the
    firing log replays identically, nothing hangs, no engine thread
    leaks, and the SLO verdicts (generous 10s deadline — a hang detector,
    not a latency bar) are identical."""
    cfg, params, tables = tiny
    import jax.numpy as jnp

    timing = VerbsTiming(t_server=2e-4)
    n_events = 240

    def capacity():
        server = FlexEMRServer(
            cfg, params, tables, num_engines=4, pipeline_depth=2,
            hedge_timeout=None, timing=timing, emulate_wire=True,
            batcher=BucketBatcher(buckets=(16,), max_wait=0.0005),
        )
        try:
            server._dense(
                jnp.zeros((16, cfg.num_fields, cfg.embed_dim), np.float32),
                jnp.zeros((16, cfg.n_dense), np.float32),
            ).block_until_ready()
            prng = np.random.default_rng(0)
            for _ in range(10 * 16):
                server.submit(_payload(prng, cfg))
            t0 = time.perf_counter()
            while server.step() is not None:
                pass
            return 10 * 16 / (time.perf_counter() - t0)
        finally:
            server.close()

    qps = 1.2 * capacity()
    events = OpenLoopGenerator(
        constant(qps, 2.0 * n_events / qps),
        RecsysPayloadFactory(cfg.tables, cfg.n_dense),
        seed=5, deadline_s=10.0, max_events=n_events,
    ).events()
    sched = FaultSchedule(faults=(
        FaultSpec("straggler_storm", at_batch=3, target=1,
                  duration_batches=3, latency_mult=8.0),
        FaultSpec("straggler_storm", at_batch=8, target=2,
                  duration_batches=3, latency_mult=8.0),
    ), seed=0)

    results = []
    for depth in (1, 2, 4):
        injector = ChaosInjector(sched)
        slo = SloMonitor(SloObjective(latency_target_s=10.0))
        server = FlexEMRServer(
            cfg, params, tables, num_engines=4, pipeline_depth=depth,
            hedge_timeout=None, timing=timing, emulate_wire=True,
            batcher=BucketBatcher(buckets=(16,), max_wait=0.0005),
            chaos=injector, slo=slo,
            retry_policy=RetryPolicy(budget_frac=0.25, seed=0),
        )
        try:
            stats = OpenLoopDriver().run(server, events)
            summ = injector.summary()
            retry = server.service.retry_summary()
        finally:
            server.close()
        engine = server.engine_summary()
        assert stats["shed"] == 0  # no admission: everything retires
        assert server.metrics.requests == n_events
        assert summ["wall"]["forced_restores"] == 0
        assert summ["active_drops"] == []
        assert engine["parked_now"] == 0 and engine["leaked_threads"] == 0
        assert retry["amplification"] <= 0.25 + 1e-9
        results.append({
            "firing_log": summ["firing_log"],
            "fired": summ["faults_fired"],
            "verdicts": (slo.deadline_met, slo.deadline_total),
        })
    assert results[0]["fired"] == len(sched.faults)
    for r in results[1:]:
        assert r["firing_log"] == results[0]["firing_log"]
        assert r["verdicts"] == results[0]["verdicts"]
    # The generous deadline is met everywhere — the verdict vector is
    # all-True at every depth, so equality above is a real hang detector.
    assert results[0]["verdicts"] == (n_events, n_events)


def test_close_reports_no_leaked_threads():
    _, _, svc = _pool_setup()
    svc.close()
    s = svc.pool.summary()
    assert s["leaked_threads"] == 0
    assert all(not t.is_alive() for t in svc.pool.threads)
