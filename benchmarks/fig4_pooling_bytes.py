"""Paper Fig 4: hierarchical pooling cuts embedding bytes on the network.

Three measurements:
  (a) host wire format — raw rows (4a) vs pushed-down partials (4b) bytes for
      zipf multi-hot traffic (HostLookupService.network_bytes);
  (b) SPMD collective bytes — baseline vs hierarchical DisaggEmbedding modes,
      parsed from compiled HLO of a small sharded lookup (the TPU-native
      restatement: the psum payload drops from [B,F,nnz,D] to [B,F,D]);
  (c) serving-path segment pushdown A/B (``run_pushdown``) — the SAME
      multi-hot zipf stream served by ``PooledLookupService`` with
      near-memory bag reduction on vs off, gated on:

        * bit-equal outputs (the partial-sum merge never perturbs results,
          including across pipeline depth 2 and a forced hedge);
        * response wire-byte reduction >= 2x (engine
          ``wire_response_bytes`` counters, not a format estimate);
        * ``runtime.simulator.compare_pushdown`` fed the *measured*
          poolable fraction and rows-per-segment predicting the measured
          byte reduction within 10% (relative) — the same closed-loop
          crosscheck dedup_bench runs, now for the pushdown model and the
          request-direction channel it exposes.

``python -m benchmarks.fig4_pooling_bytes --smoke`` runs only (c) in a
seconds-scale configuration with the gates enforced (the CI entry);
``benchmarks/run.py --smoke`` ingests the same dict as ``pushdown_smoke``.
"""
from __future__ import annotations

import argparse
import collections
import subprocess
import sys
import time

import numpy as np

from repro.core.lookup_engine import HostLookupService
from repro.core.sharding import TableSpec, make_fused_tables
from repro.data import synthetic as syn
from repro.rdma import PooledLookupService
from repro.runtime.simulator import compare_pushdown

SPMD_PROBE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.sharding import TableSpec
from repro.core.embedding import DisaggEmbedding
from repro.launch.hlo_analysis import analyze
mesh = make_mesh((2, 4), ("data", "model"))
specs = [TableSpec(f"t{i}", 100_000, nnz=8) for i in range(8)]
out = {}
for mode in ("baseline", "hierarchical"):
    emb = DisaggEmbedding(specs=specs, dim=64, num_shards=4, mode=mode)
    SDS = jax.ShapeDtypeStruct
    p = {"table": SDS((emb.sharded.total_rows, 64), jnp.float32)}
    idx = SDS((256, 8, 8), jnp.int32); msk = SDS((256, 8, 8), jnp.bool_)
    sh = lambda s: NamedSharding(mesh, s)
    comp = jax.jit(
        lambda p, i, m: emb.lookup(p, i, m, mesh=mesh),
        in_shardings=({"table": sh(P("model", None))}, sh(P("data", None, None)),
                      sh(P("data", None, None))),
    ).lower(p, idx, msk).compile()
    out[mode] = analyze(comp.as_text(), 8).collective_bytes_per_device
print(json.dumps(out))
"""


def run(batch: int = 1024, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    specs = tuple(TableSpec(f"t{i}", 100_000, nnz=8) for i in range(8))
    tables = make_fused_tables(specs, 64, 8)
    table = rng.normal(size=(tables.total_rows, 64)).astype(np.float32)
    b = syn.recsys_batch(rng, specs, batch)
    svc_raw = HostLookupService(tables, table, pushdown=False)
    svc_pd = HostLookupService(tables, table, pushdown=True)
    t0 = time.perf_counter()
    try:
        raw = svc_raw.network_bytes(b["indices"], b["mask"])
        pd = svc_pd.network_bytes(b["indices"], b["mask"])
    finally:
        svc_raw.close()
        svc_pd.close()

    import json
    import os
    import pathlib

    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    # A count over virtual CPU devices: the child never reaches for an
    # accelerator, which this process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", SPMD_PROBE], env=env, capture_output=True,
        text=True, timeout=560,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"SPMD probe child exited {proc.returncode}:\n{proc.stderr}"
        )
    spmd = json.loads(proc.stdout.strip().splitlines()[-1])
    return {
        "us_per_call": 1e6 * (time.perf_counter() - t0),
        "host_raw_bytes": raw,
        "host_pushdown_bytes": pd,
        "host_reduction": raw / max(pd, 1),
        "spmd_baseline_coll_bytes": spmd["baseline"],
        "spmd_hierarchical_coll_bytes": spmd["hierarchical"],
        "spmd_reduction": spmd["baseline"] / max(spmd["hierarchical"], 1),
    }


def _replay(tables, tnp, stream, segments: bool, depth: int = 1,
            hedge=None):
    """Serve the stream with ``depth`` lookups in flight; returns
    (outs, engine summary)."""
    svc = PooledLookupService(
        tables, tnp, num_threads=4, pushdown=True, dedup=True,
        pushdown_segments=segments,
    )
    outs = [None] * len(stream)
    try:
        pending: collections.deque = collections.deque()
        for i, b in enumerate(stream):
            pending.append(
                (i, svc.lookup_async(b["indices"], b["mask"],
                                     hedge_timeout=hedge))
            )
            if len(pending) >= depth:
                j, h = pending.popleft()
                outs[j] = h.wait()
        while pending:
            j, h = pending.popleft()
            outs[j] = h.wait()
        summary = svc.engine_summary()
    finally:
        svc.close()
    return outs, summary


def run_pushdown(seed: int = 0, smoke: bool = False) -> dict:
    """Measurement (c): serving-path segment-pushdown A/B (see module doc)."""
    t_start = time.perf_counter()
    n_batches = 8 if smoke else 32
    batch = 64
    # Multi-hot zipf: big-vocab tails keep most ids exclusive (poolable);
    # the duplicated zipf head stays on the dedup path — the composition
    # the serving default runs.
    specs = (
        TableSpec("hist", 200_000, nnz=32),
        TableSpec("item", 100_000, nnz=16),
    )
    dim, shards = 64, 4
    tables = make_fused_tables(specs, dim, shards)
    rng = np.random.default_rng(seed)
    tnp = (0.05 * rng.normal(size=(tables.total_rows, dim))).astype(
        np.float32
    )
    stream = [
        syn.recsys_batch(rng, specs, batch, alpha=1.05, cooccur_frac=0.1)
        for _ in range(n_batches)
    ]

    # ------------------------------------------------ A/B: same stream
    outs_off, s_off = _replay(tables, tnp, stream, segments=False)
    outs_on, s_on = _replay(tables, tnp, stream, segments=True)
    bit_equal = all(np.array_equal(x, y) for x, y in zip(outs_off, outs_on))
    # ... and under the pipelined + force-hedged serving shape.
    o2, _ = _replay(tables, tnp, stream[: max(4, n_batches // 2)],
                    segments=True, depth=2, hedge=0.0)
    bit_equal &= all(np.array_equal(x, y) for x, y in zip(o2, outs_off))

    byte_reduction = s_off["wire_response_bytes"] / max(
        1, s_on["wire_response_bytes"]
    )
    # Request bytes don't shrink: pushdown still posts every scattered id,
    # so the request share of the wire grows with the reduction.
    req_frac_off = s_off["wire_request_bytes"] / max(
        1, s_off["wire_response_bytes"]
    )
    req_frac_on = s_on["wire_request_bytes"] / max(
        1, s_on["wire_response_bytes"]
    )

    # ------------------------------- simulator crosscheck (within 10%)
    entry = 4 + dim * 4
    entries_off = s_off["wire_response_bytes"] / entry
    poolable_frac = s_on["pooled_rows"] / max(1.0, entries_off)
    rows_per_segment = s_on["pooled_rows"] / max(1, s_on["pooled_segments"])
    sim = compare_pushdown(
        poolable_frac=min(1.0, poolable_frac),
        rows_per_segment=rows_per_segment,
        request_bytes_per_subrequest=8.0
        * s_on["pooled_rows"] / max(1, s_on["pooled_segment_wrs"]),
        n_batches=150 if smoke else 400,
    )
    sim_err = abs(sim["byte_reduction"] - byte_reduction) / byte_reduction

    return {
        "us_per_call": 1e6 * (time.perf_counter() - t_start),
        "bit_equal": bit_equal,
        "byte_reduction": byte_reduction,
        "response_bytes_off": s_off["wire_response_bytes"],
        "response_bytes_on": s_on["wire_response_bytes"],
        "request_bytes_on": s_on["wire_request_bytes"],
        "request_frac_off": req_frac_off,
        "request_frac_on": req_frac_on,
        "pooled_segment_wrs": s_on["pooled_segment_wrs"],
        "pooled_segments": s_on["pooled_segments"],
        "pooled_rows": s_on["pooled_rows"],
        "poolable_frac": poolable_frac,
        "rows_per_segment": rows_per_segment,
        "sim_byte_reduction": sim["byte_reduction"],
        "sim_request_fraction": sim["request_fraction"],
        "sim_rel_err": sim_err,
    }


def gate_pushdown(out: dict) -> None:
    """Raise SystemExit on any pushdown gate failure (CI entry)."""
    if not out["bit_equal"]:
        raise SystemExit(
            "pushdown invariance VIOLATED: outputs moved with near-memory "
            "bag reduction"
        )
    if out["byte_reduction"] < 2.0:
        raise SystemExit(
            f"pushdown response-byte reduction regressed: "
            f"{out['byte_reduction']:.2f}x < 2.0x on multi-hot zipf"
        )
    if out["pooled_segments"] <= 0:
        raise SystemExit("pushdown dead: no segments pooled")
    if out["sim_rel_err"] > 0.10:
        raise SystemExit(
            f"simulator pushdown model off by {out['sim_rel_err']:.1%} "
            "(> 10% of the measured byte reduction)"
        )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale pushdown A/B only, gates enforced "
                    "(CI entry)")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)
    if not opts.smoke:
        for k, v in run(seed=opts.seed).items():
            print(f"{k}: {v}")
    out = run_pushdown(seed=opts.seed, smoke=opts.smoke)
    for k, v in out.items():
        print(f"{k}: {v}")
    gate_pushdown(out)


if __name__ == "__main__":
    main()
