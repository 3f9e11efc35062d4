"""Observability (end-to-end tracing + unified metrics registry).

The load-bearing contracts:
  * tracing is a pure observer — scores are bit-equal with the tracer and
    registry fully on vs off, across ``pipeline_depth`` {1, 2, 4} ×
    hedge {off, forced} × wire-dedup on/off;
  * spans are well-formed — no negative durations, every per-WR virtual
    span nests inside its batch's ``lookup_batch`` span, the serving
    thread's wall-clock spans are nested or disjoint (each stamped where
    its work started), and the Chrome export round-trips through
    ``tools/trace_export.py`` validation;
  * the trace and the metrics snapshot agree (sum-consistency): spans are
    emitted from the exact deltas the counters accumulate;
  * the registry is thread-safe under concurrent updates + snapshots, and
    a dead provider degrades to an ``.error`` key instead of killing the
    export;
  * the bounded latency histogram interpolates small-sample quantiles
    (fixing the floor-indexing p99 bias) and holds O(1) memory forever
    (P² streaming estimators past warmup).
"""
import importlib.util
import json
import pathlib
import threading

import jax
import numpy as np
import pytest

from repro.core.adaptive_cache import AdaptiveCacheController, MemoryModel
from repro.core.sharding import TableSpec, make_fused_tables
from repro.data import synthetic as syn
from repro.data.pipeline import BucketBatcher
from repro.models import recsys as R
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    P2Quantile,
    Tracer,
)
from repro.obs.trace import PID_VIRTUAL, PID_WALL, TID_RANKER, TID_VBATCH
from repro.rdma import PooledLookupService
from repro.runtime.serving import FlexEMRServer, ServeMetrics


def _trace_export():
    """Import tools/trace_export.py (standalone tool, not a package)."""
    path = (
        pathlib.Path(__file__).resolve().parents[1] / "tools"
        / "trace_export.py"
    )
    spec = importlib.util.spec_from_file_location("trace_export", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ P² estimators


def test_p2_quantile_tracks_reference():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=0.0, sigma=0.7, size=4000)
    for q in (0.5, 0.9, 0.99):
        est = P2Quantile(q)
        for x in xs:
            est.add(float(x))
        ref = float(np.quantile(xs, q))
        assert est.value() == pytest.approx(ref, rel=0.08), f"q={q}"
    with pytest.raises(ValueError):
        P2Quantile(1.0)


def test_p2_quantile_tiny_samples_interpolate():
    est = P2Quantile(0.99)
    for x in (1.0, 2.0, 3.0):
        est.add(x)
    # under 5 observations: exact interpolation over the buffer
    assert est.value() == pytest.approx(float(np.quantile([1, 2, 3], 0.99)))
    assert P2Quantile(0.5).value() == 0.0  # empty


def test_histogram_warmup_exact_then_bounded():
    h = Histogram(quantiles=(0.5, 0.99), warmup=16)
    xs = [float(i) for i in range(1, 11)]
    h.extend(xs)
    # inside warmup ANY q works, exactly interpolated
    for q in (0.25, 0.5, 0.73, 0.99):
        assert h.quantile(q) == pytest.approx(float(np.quantile(xs, q)))
    assert h._buf is not None
    h.extend(float(x) for x in range(11, 40))  # cross the warmup boundary
    assert h._buf is None  # exact buffer handed off: O(1) from here on
    assert h.count == 39 and h.min == 1.0 and h.max == 39.0
    assert h.mean == pytest.approx(np.mean(np.arange(1.0, 40.0)))
    assert h.quantile(0.5) == pytest.approx(
        float(np.quantile(np.arange(1.0, 40.0), 0.5)), rel=0.05
    )
    with pytest.raises(ValueError):
        h.quantile(0.73)  # untracked past warmup
    s = h.summary()
    assert s["count"] == 39 and s["p99"] >= s["p50"]
    with pytest.raises(ValueError):
        Histogram(warmup=3)


def test_serve_metrics_p99_interpolates_not_floors():
    """The old ``sorted(x)[int(0.99 * (len(x) - 1))]`` floor-indexed p99 of
    10 samples to the 9th value; the histogram interpolates."""
    m = ServeMetrics()
    for ms in range(1, 11):  # 1..10 ms
        m.observe_latency(ms / 1e3)
    s = m.summary()
    ref = float(np.quantile(np.arange(1.0, 11.0), 0.99))
    assert s["p99_latency_ms"] == pytest.approx(ref)  # 9.91, not 9.0
    assert s["p99_latency_ms"] > 9.0
    assert s["p50_latency_ms"] == pytest.approx(5.5)
    assert s["mean_latency_ms"] == pytest.approx(5.5)
    # bounded: no unbounded per-request list survives on the dataclass
    assert not hasattr(m, "latencies")


# ------------------------------------------------------------------ registry


def test_registry_instruments_shared_and_flattened(tmp_path):
    reg = MetricsRegistry()
    assert reg.counter("a.hits") is reg.counter("a.hits")  # get-or-create
    reg.counter("a.hits").add(3)
    reg.gauge("a.depth").set(7)
    reg.gauge("a.pull", fn=lambda: 11).set(0)  # callback wins over set
    reg.histogram("a.lat").extend([1.0, 2.0, 3.0])
    snap = reg.snapshot()
    assert snap["a.hits"] == 3.0
    assert snap["a.depth"] == 7.0
    assert snap["a.pull"] == 11.0
    assert snap["a.lat.count"] == 3 and snap["a.lat.mean"] == 2.0
    # nested provider output flattens to dotted scalars
    reg.register_provider(
        "p", lambda: {"x": {"y": 1}, "v": [4, 5], "arr": np.arange(2)}
    )
    snap = reg.snapshot()
    assert snap["p.x.y"] == 1 and snap["p.v.1"] == 5 and snap["p.arr.0"] == 0
    # re-registering replaces (no double-reporting), unregister removes
    reg.register_provider("p", lambda: {"x": 9})
    assert reg.snapshot()["p.x"] == 9
    reg.unregister_provider("p")
    assert not any(k.startswith("p.") for k in reg.snapshot())
    # a dead provider degrades to an .error key, never kills the export
    reg.register_provider("bad", lambda: 1 / 0)
    snap = reg.snapshot()
    assert "ZeroDivisionError" in snap["bad.error"]
    assert snap["a.hits"] == 3.0  # the rest of the export survived
    # save() is valid, sorted, flat JSON
    out = tmp_path / "metrics.json"
    reg.save(str(out))
    loaded = json.loads(out.read_text())
    assert loaded["a.hits"] == 3.0 and "bad.error" in loaded


def test_registry_thread_safe_under_concurrent_snapshots():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("lat")
    reg.register_provider("p", lambda: {"v": c.value})
    stop = threading.Event()
    errors = []

    def snapshotter():
        try:
            while not stop.is_set():
                snap = reg.snapshot()
                assert snap["n"] >= 0
        except Exception as exc:  # pragma: no cover - failure surface
            errors.append(exc)

    def writer():
        try:
            for i in range(2000):
                reg.counter("n").add()  # through the registry: same object
                h.add(float(i))
        except Exception as exc:  # pragma: no cover - failure surface
            errors.append(exc)

    snap_t = threading.Thread(target=snapshotter)
    writers = [threading.Thread(target=writer) for _ in range(4)]
    snap_t.start()
    for t in writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    snap_t.join()
    assert not errors
    assert reg.snapshot()["n"] == 4 * 2000  # no lost increments
    assert h.count == 4 * 2000


def test_counter_and_gauge_basics():
    c = Counter()
    c.inc()
    c.add(2.5)
    assert c.value == 3.5
    g = Gauge()
    g.set(4.0)
    assert g.value == 4.0


# -------------------------------------------------------------------- tracer


def test_tracer_bounded_and_chrome_export():
    tr = Tracer(max_events=3)
    tr.complete("a", "serve", 0.0, 1e-3, args={"batch": 0})
    tr.instant("b", "steal", 2e-3, pid=PID_VIRTUAL, tid=1)
    tr.complete("a", "serve", 3e-3, 1e-3)
    tr.instant("c", "hedge", 4e-3)  # over budget: dropped, counted
    tr.complete("a", "serve", 5e-3, 1e-3)
    assert len(tr) == 3 and tr.dropped == 2
    assert len(tr.events(name="a")) == 2
    assert len(tr.events(cat="steal")) == 1
    assert tr.events(name="a")[0]["args"] == {"batch": 0}
    chrome = tr.to_chrome()
    evs = chrome["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert {m["pid"] for m in meta} == {PID_WALL, PID_VIRTUAL}
    spans = [e for e in evs if e["ph"] == "X"]
    assert spans[0]["ts"] == pytest.approx(0.0)
    assert spans[0]["dur"] == pytest.approx(1e3)  # seconds -> microseconds
    assert chrome["otherData"]["dropped_events"] == 2
    json.dumps(chrome)  # JSON-serializable as-is


# --------------------------------------------------------- serving fixture


def _tiny_cfg():
    tables = (
        TableSpec("big", 4000, nnz=4),
        TableSpec("mid", 1000, nnz=2),
        TableSpec("small", 64, nnz=1),
    )
    return R.RecsysConfig(
        name="t", arch="dlrm", tables=tables, embed_dim=16, n_dense=13,
        bottom_mlp=(64, 16), mlp=(64, 32),
    )


def _controller(cfg):
    return AdaptiveCacheController(
        cfg.tables, cfg.embed_dim,
        MemoryModel(fixed_bytes=1 << 20, bytes_per_sample=1 << 10,
                    hbm_bytes=1 << 28),
        field_replication=False, max_rows=1024,
    )


@pytest.fixture(scope="module")
def obs_fixture():
    cfg = _tiny_cfg()
    params = R.init_params(cfg, jax.random.key(0))
    tables = make_fused_tables(cfg.tables, cfg.embed_dim, 4)
    rng = np.random.default_rng(11)
    reqs = []
    for _ in range(24):
        b = syn.recsys_batch(rng, cfg.tables, 1, n_dense=cfg.n_dense)
        reqs.append({"indices": b["indices"][0], "mask": b["mask"][0],
                     "dense": b["dense"][0]})
    return cfg, params, tables, reqs


REFRESH_EVERY = 3
REFRESH_PHASES = ("refresh_plan", "refresh_fetch", "refresh_insert",
                  "refresh_affinity")


def _serve(cfg, params, tables, reqs, depth=2, hedge=None, dedup=True,
           tracer=None, registry=None):
    server = FlexEMRServer(
        cfg, params, tables, controller=_controller(cfg),
        cache_refresh_every=REFRESH_EVERY, pipeline_depth=depth,
        hedge_timeout=hedge,
        dedup=dedup, batcher=BucketBatcher(buckets=(8,), max_wait=0.001),
        tracer=tracer, registry=registry,
    )
    try:
        for r in reqs:
            server.submit(r)
        outs = []
        while True:
            o = server.step()
            if o is None and server.metrics.requests >= len(reqs):
                break
            if o is not None:
                outs.append(o["scores"])
        metrics = server.metrics
        engine = server.engine_summary()
    finally:
        server.close()
    return outs, metrics, engine


def _ranker_spans(tracer, name=None):
    """The serving thread's wall-clock spans as (start, end, name), without
    the whole-batch span (it overlaps the pipelined batches)."""
    return [(e["ts"], e["ts"] + e["dur"], e["name"])
            for e in tracer.events(name=name)
            if e["ph"] == "X" and e["pid"] == PID_WALL
            and e["tid"] == TID_RANKER and e["name"] != "batch"]


def _assert_nested_or_disjoint(tracer, tol=1e-9):
    """Every pair of ranker-row spans (``batch`` aside) is nested or
    disjoint: what the benchmark's idle-gap labelling assumes."""
    stack = []
    for s, e, name in sorted(_ranker_spans(tracer),
                             key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s + tol:
            stack.pop()
        if stack:
            ps, pe, pname = stack[-1]
            assert e <= pe + tol, (
                f"{name} [{s}, {e}] overlaps {pname} [{ps}, {pe}]")
        stack.append((s, e, name))


# -------------------------------------------- tracing on/off bit-equality


def test_tracing_bit_equal_across_grid(obs_fixture):
    """The observability non-negotiable: for every (depth, hedge, dedup)
    cell, scores with the tracer + a fresh registry fully on are
    bit-identical to the plain run — and every cell's trace validates."""
    cfg, params, tables, reqs = obs_fixture
    te = _trace_export()
    ref, _, _ = _serve(cfg, params, tables, reqs, depth=1)
    assert len(ref) == len(reqs) // 8
    for depth in (1, 2, 4):
        for hedge in (None, 0.0):
            for dedup in (True, False):
                plain, _, _ = _serve(
                    cfg, params, tables, reqs, depth, hedge, dedup
                )
                tracer = Tracer()
                traced, _, _ = _serve(
                    cfg, params, tables, reqs, depth, hedge, dedup,
                    tracer=tracer, registry=MetricsRegistry(),
                )
                tag = f"depth={depth} hedge={hedge} dedup={dedup}"
                assert len(plain) == len(traced) == len(ref)
                for a, b, c in zip(traced, plain, ref):
                    np.testing.assert_array_equal(
                        a, b, err_msg=f"{tag}: tracing moved the scores"
                    )
                    np.testing.assert_array_equal(
                        b, c, err_msg=f"{tag}: diverged from depth-1 ref"
                    )
                assert len(tracer) > 0 and tracer.dropped == 0
                problems = te.validate(tracer.to_chrome())
                assert not problems, f"{tag}: {problems}"
                _assert_nested_or_disjoint(tracer)


# ------------------------------- well-formedness + sum-consistency + export


def test_spans_well_formed_and_sums_consistent(obs_fixture, tmp_path):
    cfg, params, tables, reqs = obs_fixture
    tracer, registry = Tracer(), MetricsRegistry()
    _, metrics, engine = _serve(
        cfg, params, tables, reqs, depth=2, hedge=0.0,
        tracer=tracer, registry=registry,
    )
    n_batches = len(reqs) // 8

    # the serving-thread span skeleton: one per batch, in every stage
    for name in ("admit", "probe", "post", "lookup_stall", "dense",
                 "batch", "merge", "tier_merge"):
        assert len(tracer.events(name=name)) == n_batches, name
    assert len(tracer.events(name="lookup_batch")) == n_batches
    assert len(tracer.events(name="wr")) > 0
    assert len(tracer.events(name="doorbell")) > 0
    for e in tracer.events():
        assert e["dur"] >= 0.0, e

    # the untraced half of the step: one heat and one account span a
    # batch, one refresh every REFRESH_EVERY batches with its four phases
    # inside it, and a poll before every admit (plus the empty polls)
    for name in ("heat", "account"):
        assert len(tracer.events(name=name)) == n_batches, name
        assert [e["args"]["batch"] for e in tracer.events(name=name)] == \
            list(range(1, n_batches + 1)), name
    refreshes = _ranker_spans(tracer, "refresh")
    assert len(refreshes) == n_batches // REFRESH_EVERY
    for phase in REFRESH_PHASES:
        inside = _ranker_spans(tracer, phase)
        assert len(inside) == len(refreshes), phase
        for (s, e, _), (ps, pe, _) in zip(inside, refreshes):
            assert ps < s and e < pe, phase
    polls = tracer.events(name="poll")
    assert sum(e["args"]["requests"] > 0 for e in polls) == n_batches
    assert sum(e["args"]["requests"] for e in polls) == len(reqs)
    # the wall-clock row is laminar, and each batch's dense stage starts
    # after its lookup stall ends (both stamped where the work started)
    _assert_nested_or_disjoint(tracer)
    stalls = _ranker_spans(tracer, "lookup_stall")
    denses = _ranker_spans(tracer, "dense")
    assert len(stalls) == len(denses) == n_batches
    for (_, stall_end, _), (dense_start, _, _) in zip(stalls, denses):
        assert dense_start >= stall_end
    # each poll ends before the admit it fed starts
    admits = _ranker_spans(tracer, "admit")
    fed = [e for e in polls if e["args"]["requests"] > 0]
    for p, (admit_start, _, _) in zip(fed, admits):
        assert p["ts"] + p["dur"] <= admit_start

    # per-WR virtual events carry the batch correlation key and nest
    # inside their batch's lookup_batch span
    batches = {
        e["args"]["batch"]: (e["ts"], e["ts"] + e["dur"])
        for e in tracer.events(name="lookup_batch")
    }
    assert all(e["tid"] == TID_VBATCH
               for e in tracer.events(name="lookup_batch"))
    for e in tracer.events(name="wr"):
        assert e["pid"] == PID_VIRTUAL
        lo, hi = batches[e["args"]["batch"]]
        assert lo - 1e-9 <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e-9

    # sum-consistency: spans are cut from the exact metric deltas
    def span_sum(name):
        return sum(e["dur"] for e in tracer.events(name=name))

    assert span_sum("lookup_stall") == pytest.approx(
        metrics.lookup_seconds, rel=1e-6, abs=1e-9
    )
    assert span_sum("dense") == pytest.approx(
        metrics.dense_seconds, rel=1e-6, abs=1e-9
    )
    assert span_sum("credit_stall") == pytest.approx(
        engine["virtual_credit_stall_s"], rel=1e-6, abs=1e-9
    )
    assert len(tracer.events(name="steal")) == engine["virtual_steals"]
    assert len(tracer.events(name="hedge_arm")) == metrics.hedges

    # the server registered every subsystem under its dotted namespace
    snap = registry.snapshot()
    for prefix in ("serve.", "tier.", "rdma.pool."):
        assert any(k.startswith(prefix) for k in snap), prefix
    assert snap["serve.requests"] == len(reqs)
    assert not any(k.endswith(".error") for k in snap)

    # export round-trip: save -> load -> validate -> summarize
    te = _trace_export()
    path = tmp_path / "serve.trace.json"
    tracer.save(str(path))
    loaded = te.load(str(path))
    assert te.validate(loaded) == []
    rows = te.summarize(loaded)
    assert any(r["stage"] == "dense" and r["count"] == n_batches
               for r in rows)
    with pytest.raises(FileNotFoundError):
        te.load(str(tmp_path / "missing.json"))


def test_refresh_insert_span_counts_rounds(obs_fixture):
    """A traced cache-plan application records the insert's commit rounds
    on ``refresh_insert``: the advance of ``HostHashCache.insert_rounds``."""
    cfg, params, tables, reqs = obs_fixture
    tracer = Tracer()
    server = FlexEMRServer(
        cfg, params, tables, controller=_controller(cfg),
        cache_refresh_every=REFRESH_EVERY,
        batcher=BucketBatcher(buckets=(8,), max_wait=0.001),
        tracer=tracer, registry=MetricsRegistry(),
    )
    try:
        for r in reqs:
            server.submit(r)
        while server.step() is not None or \
                server.metrics.requests < len(reqs):
            pass
        cache = server._tiered.cache
        rounds = cache.insert_rounds
        server._apply_cache_plan(server.metrics.batches)
        assert server._tiered.cache is cache
        args = tracer.events(name="refresh_insert")[-1]["args"]
        assert args["rounds"] >= 1
        assert cache.insert_rounds - rounds == args["rounds"]
        assert args["rows"] >= args["fresh"]
    finally:
        server.close()


def test_trace_export_flags_malformed(tmp_path):
    te = _trace_export()
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "wr", "cat": "wire", "ts": 5.0, "dur": -1.0,
         "pid": PID_VIRTUAL, "tid": 0, "args": {"batch": 0}},
    ]}))
    problems = te.validate(te.load(str(bad)))
    assert problems  # negative duration + missing metadata must be flagged
    bad.write_text("{}")
    with pytest.raises(ValueError):
        te.load(str(bad))


# ----------------------------------------------- pool summary under threads


def test_engine_pool_summary_race_free(obs_fixture):
    """summary() taken concurrently with live submissions never throws and
    its per-thread gauges stay shape-consistent; the final quiescent
    snapshot satisfies the settle-once accounting identity."""
    cfg, params, tables, reqs = obs_fixture
    rng = np.random.default_rng(5)
    tnp = (0.05 * rng.normal(size=(tables.total_rows, cfg.embed_dim))
           ).astype(np.float32)
    svc = PooledLookupService(tables, tnp, num_threads=4)
    batches = [syn.recsys_batch(rng, tables.specs, 16) for _ in range(8)]
    errors = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                s = svc.engine_summary()
                assert len(s["queue_depth"]) == 4
                assert len(s["steals_in"]) == len(s["steals_out"]) == 4
                assert s["subrequests"] >= 0
        except Exception as exc:  # pragma: no cover - failure surface
            errors.append(exc)

    t = threading.Thread(target=reader)
    t.start()
    try:
        handles = [
            svc.lookup_async(b["indices"], b["mask"], hedge_timeout=0.0)
            for b in batches
        ]
        for h in handles:
            h.wait()
    finally:
        stop.set()
        t.join()
        svc.close()
    assert not errors
    s = svc.engine_summary()
    assert s["hedge_cancelled"] + sum(s["executed"]) == \
        s["subrequests"] + s["hedged"]
    assert s["queue_depth"] == [0, 0, 0, 0]  # drained and quiescent
