"""One run of one cell: build, warm up, measure a window, check, report.

The served path under test is ``repro.runtime.serving.FlexEMRServer``,
built as ``repro.launch.serve.build`` builds it, from the configuration
file's sizes and serve options, as its head (``chipbench/heads/<arch>.py``)
describes the model to the program, with weights the head makes.  Traffic
is made whole in set-up and driven open-loop (``driver.drive``).  After the
window the plain reference (``model.reference_scores``, weights made again
from the seed) scores every request that arrived in the window.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import pathlib
import shutil
import sys
import time

import numpy as np

from chipbench import cells, devtrace, driver, model, peaks, traffic

LEAD_S = 0.05  # between traffic made and the first arrival
DRAIN_S = 60.0  # a window request may retire this long after the close
RANKER_SPANS = ("admit", "lookup_stall", "dense")  # the step's traced parts
OUT_DIR = cells.ROOT / ".chipbench"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_compile_cache() -> pathlib.Path:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment says: each checkout keeps its own."""
    import jax

    path = OUT_DIR / "jax_cache"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_server(config: dict, params: dict, tracer=None):
    """The server ``repro.launch.serve.build`` makes, from the file."""
    from repro.core.adaptive_cache import AdaptiveCacheController, MemoryModel
    from repro.core.sharding import make_fused_tables
    from repro.data.pipeline import BucketBatcher
    from repro.obs import SloMonitor, SloObjective, get_registry
    from repro.runtime.serving import FlexEMRServer

    s = config["serve"]
    rc = model.head(config).program_config(config)
    tables = make_fused_tables(rc.tables, rc.embed_dim, s["num_servers"])
    if tables.total_rows != model.total_rows(config):
        raise ValueError(f"fused table has {tables.total_rows} rows, the "
                         f"benchmark made {model.total_rows(config)}")
    controller = AdaptiveCacheController(
        rc.tables, rc.embed_dim, MemoryModel(**s["memory_model"]),
        max_rows=s["cache_rows"], field_replication=False)
    slo = SloMonitor(SloObjective(latency_target_s=1e-3 * s["slo_target_ms"]))
    return FlexEMRServer(
        rc, params, tables, controller=controller,
        num_engines=s["num_engines"], pushdown=s["pushdown"],
        engine=s["engine"], pipeline_depth=s["pipeline_depth"],
        dedup=s["dedup"], emulate_wire=s["emulate_wire"],
        cache_refresh_every=s["cache_refresh_every"],
        batcher=BucketBatcher(tuple(s["buckets"]), s["max_wait_s"]),
        tracer=tracer, registry=get_registry(), slo=slo)


def host_params(config: dict, weights: dict) -> dict:
    """The served path's params: the table copied to the host as
    ``[rows, dim]``, every other entry (the dense stage's weights) left as
    the head made it, on the device."""
    host = np.asarray(weights["emb"]["table"]).reshape(
        model.total_rows(config), config["embed_dim"])
    return {**weights, "emb": {**weights["emb"], "table": host}}


def snapshot(server) -> dict:
    """The program's counters that the per-layer readers difference."""
    m = server.metrics
    out = {
        "requests": m.requests, "batches": m.batches,
        "hits": m.cache_hits, "lookups": m.lookups,
        "bytes_network": m.bytes_network, "bytes_request": m.bytes_request,
        "queue_wait": (m.queue_wait_hist.count, m.queue_wait_hist.total),
    }
    for stage, h in m.attr_hists.items():
        out[f"attr.{stage}"] = (h.count, h.total)
    return out


@dataclasses.dataclass
class Window:
    """What a per-layer reader may read (``chipbench/metrics/*.py``)."""

    seconds: float
    marks: tuple[float, float]  # perf_counter when the window opened, closed
    start: dict
    end: dict
    drive: driver.Drive
    in_window: np.ndarray  # bool per request: arrived in the window
    spans: list  # (name, start, end) perf_counter, the serving thread
    device: devtrace.DeviceTrace | None
    end_to_end: dict
    config: dict
    chips: int
    peak: dict

    def delta(self, key: str) -> float:
        return self.end[key] - self.start[key]

    def mean_ms(self, key: str) -> float | None:
        (c0, t0), (c1, t1) = self.start[key], self.end[key]
        return 1e3 * (t1 - t0) / (c1 - c0) if c1 > c0 else None


def ranker_spans(tracer) -> list:
    """The serving thread's spans on the wall clock, perf_counter based,
    without the whole-batch span (it overlaps the pipelined batches)."""
    from repro.obs.trace import PID_WALL, TID_RANKER

    out = []
    for ev in tracer.events():
        if (ev["ph"] == "X" and ev["pid"] == PID_WALL
                and ev["tid"] == TID_RANKER and ev["name"] != "batch"):
            s = tracer.epoch + ev["ts"]
            out.append((ev["name"], s, s + ev["dur"]))
    return out


def step_untraced_share(w: Window) -> float | None:
    """Share of the window inside ``server.step()`` and outside the
    program's admit, lookup_stall and dense spans."""
    lo, hi = w.marks
    steps = devtrace.clip(map(tuple, w.drive.steps), lo, hi)
    if not steps or not w.spans:
        return None
    traced = devtrace.merge(devtrace.clip(
        [(s, e) for n, s, e in w.spans if n in RANKER_SPANS], lo, hi))
    in_step = sum(e - s for s, e in steps)
    return 100.0 * (in_step - sum(e - s for s, e in traced)) / (hi - lo)


class CompileCounter:
    """Counts compilations (backend compiles, and traces of jaxprs)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_a, **_k) -> None:
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/core/compile/jaxpr_trace_duration"):
            self.n += 1


def memory_stat(devices, key: str) -> int:
    """The largest of one ``memory_stats()`` entry over the chips."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_stat(devices, "peak_bytes_in_use")}


def percentile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if len(x) else math.nan


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices) -> dict:
    """One run; returns the result object (the line the command prints)."""
    import jax

    config, mix = cell.config, cell.traffic
    warm, tail = float(mix["warm_s"]), float(mix["tail_s"])
    limit = config["correctness"]["score_gap_limit"]
    peak = peaks.peak(devices[0].device_kind) if devices[0].platform == "tpu" \
        else {}
    compiles = CompileCounter()
    tracer = None
    if trace:
        from repro.obs import Tracer

        tracer = Tracer(max_events=4_000_000)
    parts = {"jax": time.perf_counter() - t_start}
    t_part = time.perf_counter()

    def part(name):
        nonlocal t_part
        now = time.perf_counter()
        parts[name] = now - t_part
        t_part = now

    with jax.default_matmul_precision(config["serve"]["matmul_precision"]):
        weights = model.make_weights(config, seed)
        params = host_params(config, weights)
        del weights
        part("weights")
        server = build_server(config, params, tracer)
        part("server")
        try:
            server.warmup()
            part("compile")
            tr = traffic.make_traffic(seed, config, mix, warm + seconds + tail)
            payloads = tr.payloads()
            part("traffic")
            in_window = (tr.t >= warm) & (tr.t < warm + seconds)
            need = int(np.searchsorted(tr.t, warm + seconds))
            gc.collect()
            epoch = time.perf_counter() + LEAD_S
            marks = (epoch + warm, epoch + warm + seconds)
            state = {"trace_dir": None, "ann": None}
            counters = {}

            def on_mark(i, now):
                counters[i] = (now, compiles.n, snapshot(server),
                               memory_stat(devices, "bytes_in_use"))
                if not trace:
                    return
                if i == 0:
                    state["trace_dir"] = OUT_DIR / "trace" / str(os.getpid())
                    shutil.rmtree(state["trace_dir"], ignore_errors=True)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(str(state["trace_dir"]),
                                             profiler_options=opts)
                    state["ann"] = jax.profiler.TraceAnnotation(
                        devtrace.WINDOW_ANNOTATION)
                    state["ann"].__enter__()
                    counters["ann0"] = time.perf_counter()
                else:
                    counters["ann1"] = time.perf_counter()
                    state["ann"].__exit__(None, None, None)

            d = driver.drive(server, payloads, tr.t, epoch, need, marks,
                             on_mark, give_up=marks[1] + DRAIN_S)
        finally:
            try:
                server.close()
            except Exception as exc:  # noqa: BLE001 - a failed in-flight batch
                log(f"server.close: {type(exc).__name__}: {exc}")
        if trace and state["trace_dir"] is not None:
            jax.profiler.stop_trace()
        dev = device_info(devices)
        # What the window holds on the chip, beside the process's peak,
        # which the table sets while set-up makes it (it then lives on the
        # host).
        dev["bytes_in_use_at_marks"] = [counters[i][3] for i in (0, 1)
                                        if i in counters]
        t_ref = time.perf_counter()
        window_open = counters[0][0] if 0 in counters else math.nan
        setup_s = window_open - t_start
        del server, params, payloads
        gc.collect()

        # ---- correctness: the reference on every window request served.
        w_idx = np.flatnonzero(in_window)
        served = w_idx[np.isfinite(d.retire[w_idx])]
        failed = int(len(w_idx) - len(served))
        gap = math.inf
        if len(served):
            ref_w = model.make_weights(config, seed)
            ref = model.reference_scores(config, ref_w, tr.indices[served],
                                         tr.mask[served], tr.dense[served])
            del ref_w
            gap = model.score_gap(d.scores[served], ref)
        parts["reference"] = time.perf_counter() - t_ref
    ok = (d.error is None and failed == 0 and limit is not None
          and gap <= limit)

    lat = (d.retire - d.arrival)[served]
    retired_in = np.count_nonzero((d.retire >= marks[0]) & (d.retire < marks[1]))
    e2e = {
        "p50_ms": 1e3 * percentile(lat, 50),
        "p99_ms": 1e3 * percentile(lat, 99),
        "served_rps": retired_in / seconds,
        "setup_s": setup_s,
    }
    result = {"correct": bool(ok), "attempted": int(len(w_idx)),
              "failed": failed}
    if not trace:
        metrics = {m.name: {"value": e2e[m.name], "unit": m.unit}
                   for m in cell.end_to_end}
    else:
        dtrace = None
        if state["trace_dir"] is not None and "ann1" in counters:
            try:
                dtrace = devtrace.reduce_xplane(
                    devtrace.latest_xplane(state["trace_dir"]),
                    (counters["ann0"], counters["ann1"]))
            except (FileNotFoundError, ValueError) as exc:
                log(f"device trace: {exc}")
            shutil.rmtree(state["trace_dir"], ignore_errors=True)
        w = Window(seconds, (counters[0][0], counters[1][0]), counters[0][2],
                   counters[1][2], d, in_window, ranker_spans(tracer), dtrace,
                   e2e, config, cell.chips, peak)
        metrics = {}
        for m in cell.per_layer:
            v = cells.load_reader(m.name)(w)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
        if dtrace is not None:
            dev["busy_s"] = dtrace.busy_s
            dev["window_s"] = dtrace.window_s
            idle = devtrace.complement(
                devtrace.merge(b for bs in dtrace.busy for b in bs),
                *dtrace.window)
            gaps = devtrace.label_time(w.spans, idle)
            result["breakdown"] = {
                "device_ops": sorted(dtrace.ops.items(),
                                     key=lambda kv: -kv[1])[:10],
                "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:10],
            }
    result["metrics"] = metrics
    result["device"] = dev
    result["info"] = {
        "seed": seed, "offered_rps": mix["rate_rps"],
        "compiles_in_window": (counters[1][1] - counters[0][1]
                               if 1 in counters else None),
        "error": d.error,
        "parts_s": parts,
        "end_to_end": e2e,
        "batches_before_window": (counters[0][2]["batches"]
                                  if 0 in counters else None),
        "batches_in_window": (counters[1][2]["batches"]
                              - counters[0][2]["batches"]
                              if 1 in counters else None),
        "hbm_bytes_limit": memory_stat(devices, "bytes_limit"),
        "backlog_at_marks": [
            int(np.count_nonzero(d.submit <= counters[i][0])
                - np.count_nonzero(d.retire <= counters[i][0]))
            for i in (0, 1) if i in counters],
    }
    checks = {"score_gap": {"value": gap, "limit": limit},
              "failed_requests": {"value": failed, "limit": 0}}
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return result


def dumps(result: dict) -> str:
    return json.dumps(result, default=float)
