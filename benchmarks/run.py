"""Benchmark entrypoint — one bench per paper figure + the roofline table.

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's headline
quantity), then the full §Roofline table assembled from the dry-run artifacts.

  PYTHONPATH=src python -m benchmarks.run            # full sweep
  PYTHONPATH=src python -m benchmarks.run --smoke    # seconds-scale subset

``--smoke`` runs the fast regression subset — the hotcache, prefetch, rdma,
pipeline, dedup, pushdown, obs, and loadgen benches in their shrunk
configurations — so cache-, prefetch-, engine-, pipeline-, wire-dedup-,
pooling-pushdown-, observability-, and latency-under-load regressions show
up in the bench trajectory without paying for the full figure sweep.  ``--json PATH`` additionally writes each
bench's scalar metrics for ``tools/bench_history.py`` to gate against the
committed ``benchmarks/baselines/BENCH_*.json`` snapshots.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from repro.utils import enable_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fast regression subset "
                    "(hotcache/prefetch/rdma/pipeline/dedup)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write per-bench scalar metrics as JSON "
                    "(input for tools/bench_history.py)")
    opts = ap.parse_args(argv)
    enable_compile_cache()
    rows = []
    bench_metrics: dict[str, dict] = {}

    def bench(name, fn, derive):
        try:
            out = fn()
            rows.append((name, out.get("us_per_call", 0.0), derive(out)))
            bench_metrics[name] = {
                k: v for k, v in out.items()
                if isinstance(v, (bool, int, float))
            }
            print(f"{name},{out.get('us_per_call', 0.0):.1f},{derive(out)}")
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            rows.append((name, -1, "FAILED"))
            bench_metrics[name] = {"FAILED": True}
            print(f"{name},-1,FAILED")

    def write_json():
        if opts.json is None:
            return
        ok = all(r[2] != "FAILED" for r in rows)
        with open(opts.json, "w") as f:
            json.dump({"benches": bench_metrics, "ok": ok}, f,
                      indent=1, sort_keys=True)
            f.write("\n")

    print("name,us_per_call,derived")

    from benchmarks import (
        chaos_bench,
        dedup_bench,
        hotcache_bench,
        loadgen_bench,
        obs_bench,
        overload_bench,
        pipeline_bench,
        prefetch_bench,
        rdma_bench,
    )

    hotcache_derive = lambda o: (  # noqa: E731
        f"bytes_reduction={o['bytes_reduction']:.2f}x "
        f"hit_rate={o['hit_rate']:.2f} "
        f"flat_us={o['flat_slab_us']:.0f} hash_us={o['hash_cache_us']:.0f}"
    )
    prefetch_derive = lambda o: (  # noqa: E731
        f"hit {o['hit_rate_base']:.2f}->{o['hit_rate_prefetch']:.2f} "
        f"miss_bytes={o['miss_bytes_reduction']:.2f}x "
        f"useful={o['prefetch_useful_rate']:.2f} "
        f"invariant={'ok' if o['bit_equal'] else 'VIOLATED'} "
        f"kernel={'ok' if o['kernel_matches_ref'] else 'MISMATCH'}"
    )
    rdma_derive = lambda o: (  # noqa: E731
        f"p99_speedup={o['p99_speedup']:.2f}x "
        f"steal={o['steal_speedup']:.2f}x "
        f"invariant={'ok' if o['bit_equal'] else 'VIOLATED'} "
        f"calib_t_post={o['calibrated_t_post_us']:.2f}us"
    )
    pipeline_derive = lambda o: (  # noqa: E731
        f"depth2_speedup={o['pipeline_speedup']:.2f}x "
        f"invariant={'ok' if o['bit_equal'] else 'VIOLATED'} "
        f"hedge_cancelled={o['hedge_cancelled_wrs']} "
        f"calib_err="
        f"{abs(o['calibration_achieved_util'] - o['calibration_target_util']):.3f}"
    )
    dedup_derive = lambda o: (  # noqa: E731
        f"byte_reduction={o['byte_reduction_high_skew']:.2f}x "
        f"p99={o['p99_speedup_high_skew']:.2f}x "
        f"coalesced={o['coalesced_rows']} "
        f"invariant={'ok' if o['bit_equal'] else 'VIOLATED'} "
        f"sim_err={o['sim_rel_err']:.1%}"
    )
    obs_derive = lambda o: (  # noqa: E731
        f"overhead={o['overhead_frac']:.1%} "
        f"events={o['events']} "
        f"invariant={'ok' if o['bit_equal'] else 'VIOLATED'} "
        f"sums={'ok' if o['sum_consistent'] else 'INCONSISTENT'} "
        f"trace={'ok' if o['trace_valid'] else 'INVALID'}"
    )
    chaos_derive = lambda o: (  # noqa: E731
        f"fired={o['faults_fired']} "
        f"invariant={'ok' if o['bit_equal'] else 'VIOLATED'} "
        f"hangs={'none' if o['zero_hangs'] else 'HUNG'} "
        f"p99_tail={o['p99_inflation_tail']:.2f}x"
        f"{'' if o['p99_bounded'] else ' UNBOUNDED'} "
        f"replicated={o['rows_re_replicated']} moved={o['moved_rows']}"
    )
    pushdown_derive = lambda o: (  # noqa: E731
        f"byte_reduction={o['byte_reduction']:.2f}x "
        f"segments={o['pooled_segments']} "
        f"req_frac={o['request_frac_on']:.2f} "
        f"invariant={'ok' if o['bit_equal'] else 'VIOLATED'} "
        f"sim_err={o['sim_rel_err']:.1%}"
    )
    loadgen_derive = lambda o: (  # noqa: E731
        f"capacity={o['capacity_qps']:.0f}rps "
        f"p99_knee={o['p99_knee_ms']:.1f}ms "
        f"p99_over={o['p99_overload_ms']:.1f}ms "
        f"crowd_alerts={o['crowd_alerts']} "
        f"coverage_err={o['attr_coverage_err']:.2%} "
        f"gates={'ok' if o['gates_ok'] else 'FAILED:' + ','.join(o['gates_failed'])}"
    )
    overload_derive = lambda o: (  # noqa: E731
        f"goodput_ratio={o['goodput_ratio']:.2f}x "
        f"shed={o['shed']} "
        f"retry_amp={o['retry_amplification']:.3f} "
        f"degraded={o['grid_degraded_requests']} "
        f"gates={'ok' if o['gates_ok'] else 'FAILED:' + ','.join(o['gates_failed'])}"
    )

    if opts.smoke:
        bench(
            "hotcache_smoke",
            lambda: hotcache_bench.run(smoke=True),
            hotcache_derive,
        )
        bench(
            "prefetch_smoke",
            lambda: prefetch_bench.run(smoke=True),
            prefetch_derive,
        )
        bench(
            "rdma_smoke",
            lambda: rdma_bench.run(smoke=True),
            rdma_derive,
        )
        bench(
            "pipeline_smoke",
            lambda: pipeline_bench.run(smoke=True),
            pipeline_derive,
        )
        bench(
            "dedup_smoke",
            lambda: dedup_bench.run(smoke=True),
            dedup_derive,
        )
        from benchmarks import fig4_pooling_bytes

        bench(
            "pushdown_smoke",
            lambda: fig4_pooling_bytes.run_pushdown(smoke=True),
            pushdown_derive,
        )
        bench(
            "obs_smoke",
            lambda: obs_bench.run(smoke=True),
            obs_derive,
        )
        bench(
            "loadgen_smoke",
            lambda: loadgen_bench.run(smoke=True),
            loadgen_derive,
        )
        bench(
            "chaos_smoke",
            lambda: chaos_bench.run(smoke=True),
            chaos_derive,
        )
        bench(
            "overload_smoke",
            lambda: overload_bench.run(smoke=True),
            overload_derive,
        )
        write_json()
        failed = [r for r in rows if r[2] == "FAILED"]
        if failed:
            sys.exit(1)
        return

    from benchmarks import (
        fig2_embedding_dominance,
        fig4_pooling_bytes,
        fig7_cache_contention,
        fig8_rdma,
        kernel_bench,
    )

    bench(
        "fig2_embedding_dominance",
        fig2_embedding_dominance.run,
        lambda o: f"embedding_share={o['embedding_share']:.2f}",
    )
    bench(
        "fig4_pooling_bytes",
        fig4_pooling_bytes.run,
        lambda o: (
            f"host_reduction={o['host_reduction']:.2f}x "
            f"spmd_reduction={o.get('spmd_reduction', float('nan')):.2f}x"
        ),
    )
    bench(
        "fig7_cache_contention",
        fig7_cache_contention.run,
        lambda o: (
            f"adaptive_vs_large_static={o['speedup_vs_large_static']:.2f}x "
            f"adaptive_rows={o['adaptive_rows']}"
        ),
    )
    bench(
        "fig8_rdma",
        fig8_rdma.run,
        lambda o: (
            f"engine_speedup={o['engine_speedup']:.2f}x "
            f"credit_latency_reduction={o['credit_latency_reduction']:.0%} "
            f"migration={o['migration_speedup']:.2f}x"
        ),
    )
    bench(
        "kernel_baselines",
        kernel_bench.run,
        lambda o: f"attention_us={o['attention_us']:.0f}",
    )
    bench("hotcache", hotcache_bench.run, hotcache_derive)
    bench("prefetch", prefetch_bench.run, prefetch_derive)
    bench("rdma", rdma_bench.run, rdma_derive)
    bench("pipeline", pipeline_bench.run, pipeline_derive)
    bench("dedup", dedup_bench.run, dedup_derive)
    bench(
        "pushdown",
        lambda: fig4_pooling_bytes.run_pushdown(smoke=False),
        pushdown_derive,
    )
    bench("obs", obs_bench.run, obs_derive)
    bench("loadgen", lambda: loadgen_bench.run(smoke=False), loadgen_derive)
    bench("chaos", lambda: chaos_bench.run(smoke=False), chaos_derive)
    bench(
        "overload",
        lambda: overload_bench.run(smoke=False),
        overload_derive,
    )

    print()
    try:
        from benchmarks import roofline

        roofline.main()
    except Exception:  # noqa: BLE001
        traceback.print_exc()

    # §Perf hillclimb trajectories (if the driver has been run)
    import pathlib

    hc = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "hillclimb"
    if hc.exists():
        print("\n== §Perf hillclimb iterations (experiments/hillclimb) ==")
        for f in sorted(hc.glob("*.json")):
            print(f"-- {f.stem}")
            for r in json.loads(f.read_text()):
                t = r["roofline"]
                print(
                    f"   {r['variant']:22s} comp={t['compute_s']*1e3:10.2f}ms "
                    f"mem={t['memory_s']*1e3:10.2f}ms "
                    f"coll={t['collective_s']*1e3:10.2f}ms "
                    f"gib={r['gib_per_dev']:6.2f}"
                )

    write_json()
    failed = [r for r in rows if r[2] == "FAILED"]
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
