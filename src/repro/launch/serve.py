"""Disaggregated serving driver: replay a diurnal trace through FlexEMRServer.

  PYTHONPATH=src python -m repro.launch.serve --requests 2000
  PYTHONPATH=src python -m repro.launch.serve --config dlrm-flexemr --row-cut 8

``--config`` picks the model: the small ``dlrm-serve`` (default) or the
paper's ``dlrm-flexemr`` at its full widths; ``--row-cut N`` divides every
table's rows by N, for a host or device that cannot hold the declared rows.
The dense stage is compiled for every batcher bucket before the first
request (``warmup_s`` in the summary: set-up, not serving time).

Exercises the full §3 pipeline: bucketed batching, the §3.2 rdma engine pool
(``--engine legacy`` for the pre-pool per-connection threads) with pooling
pushdown, cross-batch pipelining (``--pipeline-depth``, default 2: batch
N+1's lookup is posted before batch N's dense stage; 1 restores the closed
loop), the adaptive cache controller resizing against the load trace —
which also feeds per-shard heat into the pool's skew-aware shard->thread
dealing — pool-hedged stragglers (cancel-the-loser duplicates on another
engine thread), and the jit'd dense ranker stage.  The summary includes the
pool's virtual p50/p99, per-thread utilization, steal counts, hedge and
cancellation counts, and credit window under ``rdma_engine``.

Observability (docs/OBSERVABILITY.md): ``--trace out.json`` records every
batch's journey — admit/probe/post/stall/dense spans on the wall clock, the
per-WR schedule on the verbs virtual clock — as Chrome-trace JSON, loadable
in Perfetto as-is (and summarizable with ``tools/trace_export.py``);
``--metrics-out metrics.json`` saves the unified registry snapshot (every
subsystem's counters under one dotted namespace).

Load injection (``--arrival``): the default ``closed`` mode replays the
diurnal trace in lockstep — the client waits for the server, so queueing
delay is invisible.  ``--arrival poisson --qps 2000 --duration 10`` drives
the server open-loop with seeded Poisson arrivals at the offered rate
(requests are stamped with their intended arrival time, so queue wait is
charged to latency even when the server falls behind); ``--arrival trace
--qps-trace sched.json`` replays a piecewise-linear QPS schedule (JSON list
of ``[t_seconds, qps]`` breakpoints).  All modes attach an ``SloMonitor``
(``--slo-target-ms``, optional ``--deadline-ms``) and print its summary —
good fraction, burn rates, goodput vs raw throughput, alert count — at
exit under the ``slo.`` registry namespace.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.core.adaptive_cache import (
    AdaptiveCacheController,
    MemoryModel,
)
from repro.core.sharding import TableSpec, make_fused_tables
from repro.data import synthetic as syn
from repro.loadgen import (
    OpenLoopDriver,
    OpenLoopGenerator,
    RecsysPayloadFactory,
    constant,
)
from repro.loadgen import trace as qps_schedule_trace
from repro.models import recsys as R
from repro.obs import SloMonitor, SloObjective, Tracer, get_registry
from repro.runtime.admission import AdmissionController
from repro.runtime.serving import FlexEMRServer
from repro.utils import enable_compile_cache, logger

SERVED_CONFIGS = ("dlrm-serve", "dlrm-flexemr")


def make_serving_dlrm(scale: float = 1.0) -> R.RecsysConfig:
    tables = (
        [TableSpec(f"big_{i}", int(200_000 * scale), nnz=4) for i in range(2)]
        + [TableSpec(f"mid_{i}", int(50_000 * scale), nnz=1) for i in range(6)]
        + [TableSpec(f"small_{i}", 2_000, nnz=1) for i in range(8)]
    )
    return R.RecsysConfig(
        name="dlrm-serve",
        arch="dlrm",
        tables=tuple(tables),
        embed_dim=64,
        n_dense=13,
        bottom_mlp=(256, 64),
        mlp=(256, 128),
    )


def make_config(name: str, scale: float = 1.0, row_cut: int = 1
                ) -> R.RecsysConfig:
    """The model to serve, with every table's rows divided by `row_cut`
    (widths untouched); `scale` sizes the ad-hoc dlrm-serve only."""
    if name == "dlrm-serve":
        cfg = make_serving_dlrm(scale)
    elif name == "dlrm-flexemr":
        from repro.configs import dlrm_flexemr

        cfg = dlrm_flexemr.make_config()
    else:
        raise ValueError(f"unknown config {name!r}; served: {SERVED_CONFIGS}")
    if row_cut < 1:
        raise ValueError("row_cut must be >= 1")
    if row_cut > 1:
        cfg = dataclasses.replace(cfg, tables=tuple(
            dataclasses.replace(t, vocab=max(1, t.vocab // row_cut))
            for t in cfg.tables
        ))
    return cfg


def _build_chaos(args, tables, tracer):
    """--chaos-seed / --reshard-to -> a bound-ready ChaosInjector (or None)."""
    chaos_seed = getattr(args, "chaos_seed", None)
    reshard_to = getattr(args, "reshard_to", None)
    if chaos_seed is None and reshard_to is None:
        return None
    from repro.chaos import (
        FAULT_RESHARD,
        ChaosInjector,
        FaultSchedule,
        FaultSpec,
    )

    # Triggers are admitted-batch counts; approximate the batch budget from
    # the request budget and the mean diurnal burst (~32 requests/batch —
    # the batcher cuts variable buckets, so this only shapes *where* in
    # the run faults land; the exit summary reports what actually fired).
    n_batches = max(4, args.requests // 32)
    faults = ()
    if chaos_seed is not None:
        faults = FaultSchedule.generate(
            chaos_seed, num_batches=n_batches,
            num_engines=args.num_engines,
            num_shards=tables.num_shards,
            n_faults=args.chaos_faults,
        ).faults
    if reshard_to is not None:
        faults = faults + (FaultSpec(
            FAULT_RESHARD, at_batch=max(1, n_batches // 2),
            target=reshard_to,
        ),)
    schedule = FaultSchedule(
        faults=tuple(sorted(faults, key=lambda f: f.at_batch)),
        seed=chaos_seed if chaos_seed is not None else 0,
    )
    logger.info(
        "chaos armed: %d faults over ~%d batches (%s)",
        len(schedule.faults), n_batches,
        ", ".join(f"{f.kind}@{f.at_batch}" for f in schedule.faults),
    )
    return ChaosInjector(schedule, tracer=tracer)


def build(args) -> tuple[R.RecsysConfig, dict, FlexEMRServer]:
    """Seeded params and the FlexEMRServer that ``run`` serves, as the
    command-line options describe them."""
    cfg = make_config(args.config, args.scale, args.row_cut)
    params = R.init_params(cfg, jax.random.key(args.seed))
    tables = make_fused_tables(cfg.tables, cfg.embed_dim, args.num_servers)
    controller = AdaptiveCacheController(
        cfg.tables,
        cfg.embed_dim,
        MemoryModel(
            fixed_bytes=2 << 28, bytes_per_sample=1 << 14, hbm_bytes=1 << 30
        ),
        max_rows=args.cache_rows,
        field_replication=False,
    )
    tracer = Tracer() if getattr(args, "trace", None) else None
    slo = SloMonitor(SloObjective(
        latency_target_s=1e-3 * args.slo_target_ms,
    ))
    chaos = _build_chaos(args, tables, tracer)
    admission = (
        AdmissionController(max_queue=args.admission_queue)
        if getattr(args, "admission", False) else None
    )
    retry_policy = None
    if getattr(args, "retry_budget", None) is not None:
        from repro.rdma.verbs import RetryPolicy

        retry_policy = RetryPolicy(
            budget_frac=args.retry_budget, seed=args.seed
        )
    server = FlexEMRServer(
        cfg, params, tables, controller=controller,
        num_engines=args.num_engines, pushdown=not args.no_pushdown,
        engine=args.engine, pipeline_depth=args.pipeline_depth,
        dedup=not args.no_dedup,
        tracer=tracer, registry=get_registry(), slo=slo, chaos=chaos,
        admission=admission, retry_policy=retry_policy,
        degrade_policy=getattr(args, "degrade_policy", "strict"),
    )
    return cfg, params, server


def run(args) -> dict:
    cfg, _, server = build(args)
    rng = np.random.default_rng(args.seed)
    tracer, registry, slo = server.tracer, server.registry, server.slo
    chaos, admission = server.chaos, server.admission
    retry_policy = server.retry_policy
    deadline_s = (
        1e-3 * args.deadline_ms if args.deadline_ms is not None else None
    )
    try:
        from repro.runtime.admission import ShedError

        warmup_s = server.warmup()
        logger.info("dense stage compiled for buckets: %s", warmup_s)
        t0 = time.time()
        if args.arrival == "closed":
            sizes = syn.diurnal_batches(
                rng, args.requests // 8, base=8, peak=64
            )
            submitted = 0
            for burst in sizes:
                if submitted >= args.requests:
                    break
                for _ in range(int(burst)):
                    if submitted >= args.requests:
                        break
                    b = syn.recsys_batch(
                        rng, cfg.tables, 1, n_dense=cfg.n_dense
                    )
                    try:
                        server.submit(
                            {
                                "indices": b["indices"][0],
                                "mask": b["mask"][0],
                                "dense": b["dense"][0],
                            },
                            deadline_s=deadline_s,
                        )
                    except ShedError:
                        continue  # counted under serve.admission.*
                    submitted += 1
                while server.step() is not None:
                    pass
            while server.metrics.requests < submitted:
                if server.step() is None:
                    time.sleep(0.001)
            driver_stats = None
        else:
            if args.arrival == "trace":
                if not args.qps_trace:
                    raise SystemExit(
                        "--arrival trace requires --qps-trace PATH"
                    )
                with open(args.qps_trace) as f:
                    pts = [(float(t), float(q)) for t, q in json.load(f)]
                schedule = qps_schedule_trace(pts)
            else:  # poisson
                schedule = constant(args.qps, args.duration)
            gen = OpenLoopGenerator(
                schedule,
                RecsysPayloadFactory(cfg.tables, cfg.n_dense),
                seed=args.seed,
                deadline_s=deadline_s,
            )
            events = gen.events()
            logger.info(
                "open-loop %s: %d arrivals over %.1fs (peak %.0f qps)",
                args.arrival, len(events), schedule.duration, schedule.peak,
            )
            driver_stats = OpenLoopDriver().run(server, events)
            submitted = driver_stats["submitted"]
        wall = time.time() - t0
        out = server.metrics.summary()
        out["throughput_rps"] = submitted / wall
        out["warmup_s"] = warmup_s
        if driver_stats is not None:
            out["loadgen"] = driver_stats
        out["slo"] = slo.summary()
        if chaos is not None:
            out["chaos"] = chaos.summary()
        # Overload response: what was shed at the door, what retired as a
        # brownout partial, and what the retry ladder spent.
        if admission is not None:
            out["admission"] = server._admission_summary()
        out["degraded"] = server._degraded_summary()
        if retry_policy is not None:
            out["retry"] = server.service.retry_summary()
        eng = server.engine_summary()
        if eng is not None:
            out["rdma_engine"] = eng
            # Pushdown byte split: response vs request direction, and how
            # much of the response traffic the near-memory reduction pooled
            # away (segments pooled * rows collapsed per segment).
            resp = eng.get("wire_response_bytes", 0)
            out["pushdown"] = {
                "segment_pushdown": eng.get("segment_pushdown", False),
                "pooled_segment_wrs": eng.get("pooled_segment_wrs", 0),
                "pooled_segments": eng.get("pooled_segments", 0),
                "pooled_rows": eng.get("pooled_rows", 0),
                "wire_response_bytes": resp,
                "wire_request_bytes": eng.get("wire_request_bytes", 0),
                "request_frac": (
                    eng.get("wire_request_bytes", 0) / resp if resp else 0.0
                ),
            }
        logger.info("serve summary: %s", json.dumps(out, indent=1))
        if tracer.enabled:
            tracer.save(args.trace)
            logger.info(
                "trace: %d events -> %s (open in https://ui.perfetto.dev)",
                len(tracer), args.trace,
            )
        if getattr(args, "metrics_out", None):
            registry.save(args.metrics_out)
            logger.info("metrics snapshot -> %s", args.metrics_out)
        return out
    finally:
        server.close()


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=SERVED_CONFIGS, default="dlrm-serve",
                    help="model to serve: the small dlrm-serve or the "
                    "paper's dlrm-flexemr at its full widths")
    ap.add_argument("--row-cut", type=int, default=1, metavar="N",
                    help="divide every table's rows by N (widths kept)")
    ap.add_argument("--requests", type=int, default=1000)
    ap.add_argument("--num-servers", type=int, default=8)
    ap.add_argument("--num-engines", type=int, default=4,
                    help="engine-pool threads (pooled) / I/O threads (legacy)")
    ap.add_argument("--engine", choices=("pooled", "legacy"), default="pooled",
                    help="§3.2 rdma engine pool (default) or the legacy "
                    "per-connection RdmaEngine threads")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="batches in flight: N+1's lookup posts before N's "
                    "dense stage runs (1 = closed loop, no overlap)")
    ap.add_argument("--cache-rows", type=int, default=65536)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="table-size multiplier of dlrm-serve")
    ap.add_argument("--no-pushdown", action="store_true",
                    help="disable pooling pushdown (near-memory segment "
                    "reduction on the miss path); lookups ship raw rows "
                    "and pool ranker-side — outputs are bit-equal either "
                    "way")
    ap.add_argument("--no-dedup", action="store_true",
                    help="disable the §3.1.1 wire dedup (unique-row "
                    "subrequests + in-flight coalescing + range WRs); "
                    "outputs are bit-equal either way")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="record per-batch spans + per-WR events and save "
                    "Chrome-trace JSON here (Perfetto-loadable; see "
                    "docs/OBSERVABILITY.md)")
    ap.add_argument("--metrics-out", type=str, default=None, metavar="PATH",
                    help="save the unified metrics-registry snapshot "
                    "(flat dotted-name JSON) here at exit")
    ap.add_argument("--arrival", choices=("closed", "poisson", "trace"),
                    default="closed",
                    help="closed (default): lockstep diurnal replay; "
                    "poisson: open-loop seeded Poisson arrivals at --qps "
                    "for --duration; trace: open-loop replay of the "
                    "--qps-trace schedule")
    ap.add_argument("--qps", type=float, default=1000.0,
                    help="offered rate for --arrival poisson (req/s)")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="open-loop run length in seconds "
                    "(--arrival poisson)")
    ap.add_argument("--qps-trace", type=str, default=None, metavar="PATH",
                    help="JSON list of [t_seconds, qps] breakpoints for "
                    "--arrival trace (piecewise-linear)")
    ap.add_argument("--slo-target-ms", type=float, default=50.0,
                    help="latency objective for the SLO monitor; its "
                    "summary (good fraction, burn rates, goodput, alerts) "
                    "prints at exit")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="stamp every request with this deadline; goodput "
                    "then counts deadline-met completions")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a seeded fault schedule (engine kill, "
                    "shard drop + cache-tier re-replication, straggler "
                    "storm, live reshard) during the run; the chaos "
                    "summary prints at exit.  Pooled engine only")
    ap.add_argument("--chaos-faults", type=int, default=4,
                    help="number of faults FaultSchedule.generate draws "
                    "for --chaos-seed")
    ap.add_argument("--reshard-to", type=int, default=None, metavar="N",
                    help="live-reshard the embedding tier to N shards "
                    "mid-run (quiesce-free, under traffic); composes "
                    "with --chaos-seed")
    ap.add_argument("--admission", action="store_true",
                    help="deadline-aware admission control: shed requests "
                    "whose deadline is expired or unmeetable, bound the "
                    "submit queue, and adapt the pipeline depth under "
                    "sustained SLO alerts (serve.admission.* at exit)")
    ap.add_argument("--admission-queue", type=int, default=256,
                    help="bounded submit-queue size for --admission")
    ap.add_argument("--retry-budget", type=float, default=None,
                    metavar="FRAC",
                    help="arm the per-WR retry/timeout/backoff ladder with "
                    "this retry budget (fraction of primary WRs; hedges "
                    "charge it too).  Bit-equal to off when no fault "
                    "fires.  Pooled engine only")
    ap.add_argument("--degrade-policy", default="strict",
                    choices=("strict", "degrade", "block"),
                    help="dropped-shard cold-row policy: strict parks "
                    "until restore (default), degrade answers the cache "
                    "tier's best partial and flags the request, block "
                    "fails fast.  Pooled engine only")
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
