"""Quickstart: the disaggregated embedding core in 60 lines.

  PYTHONPATH=src python examples/quickstart.py

Builds a sharded embedding over a small device mesh (set
XLA_FLAGS=--xla_force_host_platform_device_count=8 for a real mesh; falls
back to the single-device oracle otherwise), compares the paper's two lookup
paths, attaches a hot-row cache, and shows the range routing table.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DisaggEmbedding,
    RangeRouter,
    TableSpec,
    make_cache_from_table,
    make_fused_tables,
)
from repro.data import synthetic as syn


def main():
    n_dev = jax.device_count()
    mesh = None
    if n_dev >= 8:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, n_dev // 2), ("data", "model"))
        print(f"mesh: {dict(mesh.shape)}")
    else:
        print("single device -> oracle path (set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 for a mesh)")

    # Three sparse fields: one multi-hot history, two categorical ids.
    specs = (
        TableSpec("history", 100_000, nnz=8),
        TableSpec("user_geo", 5_000, nnz=1),
        TableSpec("item_cat", 300, nnz=1, pooling="mean"),
    )
    shards = mesh.shape["model"] if mesh else 1
    rng = np.random.default_rng(0)
    batch = syn.recsys_batch(rng, specs, 32)
    idx, msk = jnp.asarray(batch["indices"]), jnp.asarray(batch["mask"])

    for mode in ("baseline", "hierarchical"):
        emb = DisaggEmbedding(specs=specs, dim=32, num_shards=shards, mode=mode)
        params = emb.init(jax.random.key(0))
        pooled = jax.jit(lambda p, i, m: emb.lookup(p, i, m, mesh=mesh))(
            params, idx, msk
        )
        print(f"{mode:13s}: pooled {pooled.shape}, |x|={float(jnp.abs(pooled).mean()):.4f}")

    # Hot-row cache (the adaptive controller usually picks these ids).
    emb = DisaggEmbedding(specs=specs, dim=32, num_shards=shards)
    params = emb.init(jax.random.key(0))
    hot = np.arange(256)  # zipf-hot rows are the small ids
    cache = make_cache_from_table(emb, params, hot, 256, mesh=mesh)
    cached = jax.jit(lambda p, i, m, c: emb.lookup(p, i, m, mesh=mesh, cache=c))(
        params, idx, msk, cache
    )
    plain = emb.lookup_reference(params, idx, msk)
    print("cached path max err vs oracle:",
          float(jnp.abs(cached - plain).max()))

    # The paper's range routing table.
    tables = make_fused_tables(specs, 32, max(shards, 4))
    router = RangeRouter(tables)
    print("routing table <(start,end) -> server>:")
    for rng_, srv in router.routing_table()[:4]:
        print(f"  {rng_} -> server {srv}")

    # §3.2: the same lookup through the multi-threaded rdma engine pool —
    # host-DRAM embedding servers, per-thread queue pairs, work stealing.
    # Pooled outputs are bit-equal at every thread count; only the (virtual)
    # latency moves.
    from repro.rdma import PooledLookupService

    table_np = np.asarray(params["table"])[: tables.total_rows]
    if len(table_np) < tables.total_rows:  # pad to the fused layout
        table_np = np.pad(
            table_np, ((0, tables.total_rows - len(table_np)), (0, 0))
        )
    idx_np, msk_np = np.asarray(idx), np.asarray(msk)
    pooled = {}
    for n_threads in (1, 4):
        svc = PooledLookupService(tables, table_np, num_threads=n_threads)
        try:
            pooled[n_threads] = svc.lookup(idx_np, msk_np)
            s = svc.engine_summary()
        finally:
            svc.close()
        print(
            f"rdma pool x{n_threads}: p99 lookup {s['p99_latency_us']:.1f}us "
            f"(virtual), {s['subrequests']} subrequests, "
            f"{s['virtual_steals']} steals"
        )
    print("engine-pool invariance (1 vs 4 threads): bit_equal =",
          np.array_equal(pooled[1], pooled[4]))

    # Cross-batch pipelining: lookup_async posts the subrequests and hands
    # back a future-like handle; post batch N+1 before waiting on batch N
    # and the pool overlaps the two (the serving loop's pipeline_depth).
    # The deferred merge is identical, so the bits never move.
    svc = PooledLookupService(tables, table_np, num_threads=4)
    try:
        h0 = svc.lookup_async(idx_np, msk_np)  # batch N posted...
        h1 = svc.lookup_async(idx_np, msk_np)  # ...N+1 posted before N waits
        overlapped = [h0.wait(), h1.wait()]
    finally:
        svc.close()
    print("pipelined lookup_async (2 in flight): bit_equal =",
          all(np.array_equal(o, pooled[4]) for o in overlapped))


if __name__ == "__main__":
    main()
