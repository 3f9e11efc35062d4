"""Bring-up smoke test on one TPU chip: the served path and every kernel.

  python chip_smoke.py

Serve phase: builds the server exactly as ``python -m repro.launch.serve
--config dlrm-flexemr --row-cut N`` does — the paper's model at its full
widths, every table's rows divided by the smallest N for which the measured
HBM and host RAM hold it — compiles the dense stage for every batcher
bucket, then drives one closed-loop wave per bucket (32 .. 1024 requests,
2016 in all) through ``FlexEMRServer.submit/step``.  Every request must
retire with a finite score that matches ``models.recsys.forward(cfg,
params, batch, mesh=None)`` run on the chip for the same seeded params and
requests.

Kernel phase: each Pallas kernel runs compiled (``interpret=False``) at
serving widths and is checked against its ref.py oracle.

Exits non-zero, printing no result line, when JAX finds no TPU or any check
fails.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SEED = 0
CONFIG = "dlrm-flexemr"
ROW_CUTS = (1, 2, 4, 8, 16, 32, 64)
# Share of the measured HBM a configuration may plan to use: the rest is
# headroom for the server's dense-stage programs and allocator slack.
HBM_SHARE = 0.9


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def _host_available_bytes() -> int:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("MemAvailable missing from /proc/meminfo")


def _reference_fn(cfg):
    import jax

    from repro.models import recsys as R

    return jax.jit(lambda p, b: R.forward(cfg, p, b, mesh=None))


def _abstract_batch(cfg, batch: int) -> dict:
    import jax
    import jax.numpy as jnp

    F, nnz = cfg.num_fields, cfg.max_nnz
    return {
        "indices": jax.ShapeDtypeStruct((batch, F, nnz), jnp.int32),
        "mask": jax.ShapeDtypeStruct((batch, F, nnz), jnp.bool_),
        "dense": jax.ShapeDtypeStruct((batch, cfg.n_dense), jnp.float32),
    }


def choose_row_cut(device, largest_bucket: int) -> int:
    """Smallest cut whose table fits the host twice over and whose largest
    device program — the reference forward, which also holds the table —
    compiles within HBM_SHARE of the chip's memory."""
    import jax

    from repro.launch import serve
    from repro.models import recsys as R

    hbm = device.memory_stats()["bytes_limit"]
    host = _host_available_bytes()
    _say("hbm_bytes_limit", hbm)
    _say("host_mem_available_bytes", host)
    for cut in ROW_CUTS:
        cfg = serve.make_config(CONFIG, row_cut=cut)
        params = R.abstract_params(cfg)
        table = params["emb"]["table"]
        table_bytes = table.size * table.dtype.itemsize
        if 2 * table_bytes > host:
            _say(f"row_cut 1/{cut}", f"table {table_bytes} B: host too small")
            continue
        try:
            compiled = _reference_fn(cfg).lower(
                params, _abstract_batch(cfg, largest_bucket)
            ).compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            _say(f"row_cut 1/{cut}", "reference does not fit HBM")
            continue
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        _say(f"row_cut 1/{cut}",
             f"table {table_bytes} B, reference needs {need} B on device")
        if need <= HBM_SHARE * hbm:
            return cut
    raise RuntimeError("no row cut fits this chip")


def _serve_wave(server, batch: dict, n: int, timeout_s: float = 300.0):
    """Submit n requests, step until all retire: (scores [n], buckets cut)."""
    for i in range(n):
        server.submit({k: batch[k][i] for k in ("indices", "mask", "dense")})
    scores, cut = [], set()
    deadline = time.perf_counter() + timeout_s
    while len(scores) < n:
        _check(time.perf_counter() < deadline, "wave retired in time")
        out = server.step()
        if out is None:
            continue
        cut.add(out["bucket"])
        k = len(out["degraded"])  # requests in the batch, padding excluded
        scores.extend(np.asarray(out["scores"])[:k].tolist())
    return np.asarray(scores), cut


def serve_phase(device) -> None:
    import jax

    from repro.data import synthetic as syn
    from repro.data.pipeline import BucketBatcher
    from repro.launch import serve

    buckets = BucketBatcher().buckets
    cut = choose_row_cut(device, buckets[-1])
    args = serve.build_parser().parse_args(
        ["--config", CONFIG, "--row-cut", str(cut), "--seed", str(SEED)]
    )
    t0 = time.perf_counter()
    cfg, params, server = serve.build(args)
    build_s = time.perf_counter() - t0
    rows = params["emb"]["table"].shape[0]
    _say("config", f"{cfg.name}: {cfg.num_fields} fields x dim "
         f"{cfg.embed_dim}, bottom MLP {cfg.bottom_mlp}, top MLP "
         f"{cfg.mlp + (1,)}, row cut 1/{cut} -> {rows} rows "
         f"({rows * cfg.embed_dim * 4} B f32)")
    _say("build_s", round(build_s, 3))
    rng = np.random.default_rng(SEED)
    reference = _reference_fn(cfg)
    keys = ("indices", "mask", "dense")
    served, ref, used = [], [], set()
    submitted, ref_s = 0, 0.0
    try:
        warm = server.warmup()
        _say("dense_compile_s", {b: round(s, 3) for b, s in warm.items()})
        _check(sorted(warm) == list(buckets), "every bucket compiled")
        for bucket in buckets:
            # One wave: exactly `bucket` requests queued before the first
            # step, so the batcher cuts one batch of that bucket.  A wave
            # the batcher's 2 ms poll happens to split is served again.
            for _ in range(3):
                batch = syn.recsys_batch(rng, cfg.tables, bucket,
                                         n_dense=cfg.n_dense)
                scores, cut_buckets = _serve_wave(server, batch, bucket)
                submitted += bucket
                used |= cut_buckets
                served.append(scores)
                t1 = time.perf_counter()
                ref.append(np.asarray(
                    reference(params, {k: batch[k] for k in keys})))
                ref_s += time.perf_counter() - t1
                if bucket in cut_buckets:
                    break
        last = {k: batch[k] for k in keys}
        # What the embeddings contribute to the scores: the reference with
        # every lookup masked out, on the last (largest) wave.
        bare = np.asarray(reference(
            params, {**last, "mask": np.zeros_like(last["mask"])}))
        with jax.default_matmul_precision("default"):
            at_default = np.asarray(_reference_fn(cfg)(params, last))
    finally:
        server.close()
    served_all, ref_all = np.concatenate(served), np.concatenate(ref)
    _say("requests_retired", f"{len(served_all)} of {submitted} submitted")
    _check(len(served_all) == submitted, "every request retired")
    _check(bool(np.isfinite(served_all).all()), "every score finite")
    _say("buckets_served", sorted(used))
    _check(used == set(buckets), "every bucket served")
    err = np.abs(served_all - ref_all)
    rel = err / np.maximum(np.abs(ref_all), 1e-30)
    emb_effect = float(np.abs(ref[-1] - bare).max())
    # Tolerance.  The served scores pool each bag in float64 on the host
    # and round once to float32; the reference pools in float32 on the
    # chip, so the pooled inputs differ by about one float32 ulp.  Both
    # dense stages run the same ops at float32 matmul precision (main),
    # which carries that difference through at float32 scale: orders of
    # magnitude below what the embeddings contribute to a score at all
    # (emb_effect), while one wrong row or field moves a score by a sizable
    # share of it.
    tol = 1e-3 * emb_effect
    _say("score_max_abs_err", float(err.max()))
    _say("score_max_rel_err", float(rel.max()))
    _say("embedding_effect_max_abs", emb_effect)
    _say("score_tolerance_abs", f"{tol} (1e-3 of the embedding effect)")
    _say("default_precision_reference_max_abs_dev",
         float(np.abs(at_default - ref[-1]).max()))
    _say("reference_s", round(ref_s, 3))
    _check(emb_effect > 0, "embeddings move the scores")
    _check(float(err.max()) <= tol, "served scores match the reference")


def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp

    from repro.hotcache import ref as HREF
    from repro.hotcache.kernels import probe_gather_pool, scatter_update
    from repro.hotcache.table import EMPTY_KEY, cache_insert, empty_hash_cache
    from repro.kernels import ref as KREF
    from repro.kernels.dot_interaction import dot_interaction
    from repro.kernels.embedding_bag import embedding_bag, unpack_rows
    from repro.prefetch.kernels import topk_neighbor_select
    from repro.prefetch.ref import topk_neighbor_select_ref

    rng = np.random.default_rng(SEED)
    C, D, B, F, nnz = 65536, 64, 1024, 26, 4
    bags = B * F
    N = bags * nnz

    def report(name, t0, err):
        _say(f"kernel {name}", f"max_abs_err {err} ({time.perf_counter() - t0:.3f} s)")

    # hash-cache probe + gather + pool, half the slots resident
    t0 = time.perf_counter()
    ins = rng.choice(1 << 30, C // 2, replace=False).astype(np.int32)
    state, _ = cache_insert(
        empty_hash_cache(C, D), jnp.asarray(ins),
        jnp.asarray(rng.normal(size=(len(ins), D)).astype(np.float32)),
        jnp.asarray(rng.integers(1, 9, len(ins)).astype(np.int32)), 1,
    )
    q = rng.choice(ins, N).astype(np.int32)
    cold = rng.random(N) < 0.4
    q[cold] = rng.integers(1 << 30, EMPTY_KEY, int(cold.sum()))
    q[rng.random(N) < 0.1] = EMPTY_KEY
    w = np.where(rng.random(N) > 0.2, rng.random(N), 0.0).astype(np.float32)
    pooled, miss = probe_gather_pool(state, jnp.asarray(q), jnp.asarray(w),
                                     bags)
    want_p, want_m = HREF.probe_gather_pool_ref(
        state.keys, unpack_rows(state.rows, D), jnp.asarray(q),
        jnp.asarray(w), bags, 8,
    )
    _check(np.array_equal(np.asarray(miss), np.asarray(want_m)),
           "probe miss mask bit-equal")
    np.testing.assert_allclose(pooled, want_p, rtol=1e-5, atol=1e-6)
    report("probe_gather_pool", t0,
           float(jnp.abs(pooled - want_p).max()))

    # swap-in scatter, neighbours sharing 128-lane lines
    t0 = time.perf_counter()
    K = 4096
    pairs = 2 * rng.choice(C // 2, K // 2, replace=False)
    slots = rng.permutation(np.concatenate([pairs, pairs + 1])).astype(np.int32)
    values = jnp.asarray(rng.normal(size=(C // 2, 2 * D)).astype(np.float32))
    rows = jnp.asarray(rng.normal(size=(K, D)).astype(np.float32))
    want = np.asarray(HREF.scatter_update_ref(
        unpack_rows(values, D), jnp.asarray(slots), rows))
    got = scatter_update(values, jnp.asarray(slots), rows)
    _check(np.array_equal(np.asarray(unpack_rows(got, D)), want),
           "scatter_update bit-equal")
    report("scatter_update", t0, 0.0)

    # embedding bag over a line-packed 1M-row table
    t0 = time.perf_counter()
    V = 1 << 20
    lines = jax.random.normal(jax.random.key(SEED), (V // 2, 2 * D))
    idx = jnp.asarray(rng.integers(0, V, N).astype(np.int32))
    wb = jnp.asarray((rng.random(N) > 0.25).astype(np.float32))
    got = embedding_bag(lines, idx, wb, bags, dim=D)
    want = KREF.embedding_bag_ref(unpack_rows(lines, D), idx, wb, bags)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    report("embedding_bag", t0, float(jnp.abs(got - want).max()))

    # prefetch top-k, with absent candidates and exact ties
    t0 = time.perf_counter()
    s = rng.normal(size=(4096, 200)).astype(np.float32)
    s[rng.random(s.shape) < 0.25] = -np.inf
    s[:, :4] = 1.5
    kv, ki = topk_neighbor_select(jnp.asarray(s), 16)
    rv, ri = topk_neighbor_select_ref(jnp.asarray(s), 16)
    _check(np.array_equal(np.asarray(kv), np.asarray(rv))
           and np.array_equal(np.asarray(ki), np.asarray(ri)),
           "top-k bit-equal")
    report("topk_neighbor_select", t0, 0.0)

    # DLRM dot interaction, F = 26 fields + the bottom-MLP vector
    t0 = time.perf_counter()
    x = jnp.asarray(rng.normal(size=(B, F + 1, D)).astype(np.float32))
    got = dot_interaction(x)
    with jax.default_matmul_precision("float32"):
        want = KREF.dot_interaction_ref(x)
    err = float(jnp.abs(got - want).max())
    # The MXU may take float32 operands as one bfloat16 pass: 2^-8 relative
    # per operand, summed over D terms of the largest (diagonal) products.
    _check(err <= 2 ** -7 * float(jnp.abs(want).max()),
           "dot_interaction within one bfloat16 pass of float32")
    report("dot_interaction", t0, err)


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU: JAX found {device.platform}", file=sys.stderr)
        return 1
    from repro.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    _say("device_kind", device.device_kind)
    _say("jax", jax.__version__)
    _say("compile_cache_dir", cache_dir)
    t0 = time.perf_counter()
    # The serve phase runs at float32 matmul precision, server and reference
    # alike.  At the TPU's default precision a float32 matmul takes its
    # operands as bfloat16, and the pooling's ~1-ulp difference between the
    # two sides would flip those roundings at random, moving the scores by
    # several percent of what the embeddings contribute to them at all: the
    # comparison could then not tell a wrong row from rounding.
    with jax.default_matmul_precision("float32"):
        serve_phase(device)
    kernel_phase()
    _say("compile_cache", f"{events['hits']} hits, {events['misses']} misses")
    _say("peak_hbm_bytes", device.memory_stats()["peak_bytes_in_use"])
    _say("total_s", round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
