"""Hotcache bench: flat-slab vs hash-cache lookup, and bytes over the wire.

Three measurements, one per layer of the repro/hotcache subsystem:

  1. device lookup latency — jitted DisaggEmbedding.lookup with the seed's
     flat sorted-slab HotCacheState vs the open-addressing HashCacheState
     (same hot set, same traffic).  On TPU the hash path additionally fuses
     probe+gather+pool in one Pallas kernel; here the comparison is the data
     structure itself.
  2. wire bytes — TieredLookupService on zipf-skewed traffic vs the same
     batches with no cache: hit rate and the bytes-reduction factor
     (the ISSUE's >= 2x acceptance quantity, also asserted in tests).
  3. simulator sweep — runtime.simulator.compare_hit_rates: closed-loop
     lookup throughput as the cache hit rate rises (Fig-7/8-style axis).

``run(smoke=True)`` shrinks every dimension so `benchmarks/run.py --smoke`
can exercise the whole path in seconds.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.embedding import (
    DisaggEmbedding,
    make_cache_from_table,
    make_hash_cache_from_table,
)
from repro.core.lookup_engine import HostLookupService
from repro.core.sharding import TableSpec, make_fused_tables
from repro.data import synthetic as syn
from repro.hotcache.miss_path import TieredLookupService
from repro.hotcache.policy import AdmissionPolicy
from repro.runtime.simulator import compare_hit_rates


def _time_jit(fn, *args, iters: int) -> float:
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(seed: int = 0, smoke: bool = False) -> dict:
    rng = np.random.default_rng(seed)
    B = 32 if smoke else 128
    batches = 12 if smoke else 24
    iters = 5 if smoke else 30
    specs = (
        TableSpec("hist", 8_000 if smoke else 200_000, nnz=8),
        TableSpec("item", 4_000 if smoke else 50_000, nnz=4),
        TableSpec("geo", 512, nnz=1, pooling="mean"),
    )
    dim, shards = 32, 4
    emb = DisaggEmbedding(specs=specs, dim=dim, num_shards=shards)
    params = emb.init(jax.random.key(0))
    cap = 2048 if smoke else 16_384

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    b = syn.recsys_batch(rng, specs, B, alpha=1.35)
    idx, msk = jnp.asarray(b["indices"]), jnp.asarray(b["mask"])

    # hot set = most popular fused rows (zipf -> small ids are hot)
    offs = emb.sharded.field_offsets_array()
    fused = b["indices"].astype(np.int64) + offs[None, :, None]
    hot_ids, counts = np.unique(fused[b["mask"]], return_counts=True)
    order = np.argsort(-counts)[:cap]
    hot_ids, hot_freqs = hot_ids[order], counts[order]

    flat = make_cache_from_table(emb, params, hot_ids, cap, mesh=mesh)
    hashed = make_hash_cache_from_table(
        emb, params, hot_ids, cap * 2, freqs=hot_freqs, mesh=mesh
    )

    look = jax.jit(
        lambda p, i, m, c: emb.lookup(p, i, m, mesh=mesh, cache=c)
    )
    flat_us = _time_jit(look, params, idx, msk, flat, iters=iters)
    hash_us = _time_jit(look, params, idx, msk, hashed, iters=iters)

    # ------------------------------------------------------------ wire bytes
    tables = make_fused_tables(specs, dim, shards)
    svc = HostLookupService(tables, np.asarray(params["table"]))
    tiered = TieredLookupService(
        svc,
        num_slots=cap * 2,
        policy=AdmissionPolicy(admission_threshold=1.5, max_swap_in=cap),
        refresh_every=2,
    )
    try:
        for _ in range(max(4, batches // 3)):  # warmup
            w = syn.recsys_batch(rng, specs, B, alpha=1.35)
            tiered.lookup(w["indices"], w["mask"])
        tiered.stats = type(tiered.stats)()
        for _ in range(batches):
            w = syn.recsys_batch(rng, specs, B, alpha=1.35)
            tiered.lookup(w["indices"], w["mask"])
        s = tiered.stats
    finally:
        svc.close()

    moved = s.bytes_network + s.bytes_swap_in
    # Fig-4(a) raw-row regime (512 KiB responses): the wire is the bottleneck,
    # which is where the cache's miss-rate byte scaling shows up end to end.
    sim = compare_hit_rates(
        hit_rates=(0.0, 0.9),
        n_batches=200 if smoke else 1000,
        bytes_per_subrequest=524288.0,
    )
    return {
        "us_per_call": hash_us,
        "flat_slab_us": flat_us,
        "hash_cache_us": hash_us,
        "hit_rate": s.hit_rate,
        "bytes_no_cache": s.bytes_no_cache,
        "bytes_moved": moved,
        "bytes_reduction": s.bytes_no_cache / max(1, moved),
        "sim_speedup_at_90pct_hit": sim["speedup_at_max_hit"],
    }


if __name__ == "__main__":
    print(run())
