"""Find a cell's knee once, by a sweep of offered rates in one process.

  python3 chipbench/sweep.py --workload <cell> --seed <n> --rates 1500:4000:250

For each rate, in increasing order, on one server: ``--warm`` seconds of
traffic at that rate, then a ``--seconds`` window, then a drain with no
arrivals.  The knee is the highest offered rate at which the backlog
(submitted, not retired) at the window's end is no larger than at its
start plus one largest bucket.  Prints one JSON line per rate and the knee
last.  Needs a TPU, like ``run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402


def knee(points, largest_bucket: int):
    """Highest offered rate whose backlog grew by at most one largest
    bucket over the window; ``points`` are (rate, backlog at the window's
    start, backlog at its end).  None when no rate holds."""
    held = [r for r, b0, b1 in points if b1 <= b0 + largest_bucket]
    return max(held) if held else None


def rates_arg(s: str) -> list[float]:
    lo, hi, step = (float(x) for x in s.split(":"))
    return list(np.arange(lo, hi + step / 2, step))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=rates_arg, required=True,
                    help="lo:hi:step in requests per second")
    ap.add_argument("--warm", type=float, default=5.0)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from chipbench import cells, driver, harness, model, traffic

    cell = cells.resolve(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"no TPU: JAX found {devices[0].platform}")
        return 2
    harness.use_compile_cache()
    config = cell.config
    largest = max(config["serve"]["buckets"])
    points = []
    with jax.default_matmul_precision(config["serve"]["matmul_precision"]):
        params = harness.host_params(config,
                                     model.make_weights(config, args.seed))
        server = harness.build_server(config, params)
        try:
            server.warmup()
            harness.log(f"set-up {time.perf_counter() - T_START:.1f} s")
            for k, rate in enumerate(args.rates):
                tr = traffic.make_traffic(args.seed + k, config, cell.traffic,
                                          args.warm + args.seconds, rate=rate)
                epoch = time.perf_counter() + harness.LEAD_S
                marks = (epoch + args.warm, epoch + args.warm + args.seconds)
                at = {}
                d = driver.drive(server, tr.payloads(), tr.t, epoch, len(tr),
                                 marks, lambda i, now: at.__setitem__(i, now),
                                 give_up=marks[1])
                if d.error:
                    raise RuntimeError(d.error)

                def backlog(t):
                    return int(np.count_nonzero(d.submit <= t)
                               - np.count_nonzero(d.retire <= t))
                b0, b1 = backlog(at[0]), backlog(at[1])
                done = np.count_nonzero((d.retire >= marks[0])
                                        & (d.retire < marks[1]))
                lat = (d.retire - d.arrival)[np.isfinite(d.retire)]
                # Drain what is still queued or in flight before the next rate.
                left = int(np.count_nonzero(np.isfinite(d.submit))
                           - np.count_nonzero(np.isfinite(d.retire)))
                t0 = time.perf_counter()
                while left > 0 and time.perf_counter() - t0 < 120:
                    out = server.step()
                    if out is not None:
                        left -= len(out["degraded"])
                points.append((rate, b0, b1))
                print(json.dumps({
                    "rate_rps": rate, "backlog_start": b0, "backlog_end": b1,
                    "completed_rps": done / args.seconds,
                    "p50_ms": 1e3 * float(np.percentile(lat, 50)),
                    "p99_ms": 1e3 * float(np.percentile(lat, 99)),
                    "holds": b1 <= b0 + largest,
                    "drain_s": time.perf_counter() - t0}), flush=True)
        finally:
            server.close()
    print(json.dumps({"workload": cell.name, "knee_rps": knee(points, largest),
                      "rule": f"backlog end <= start + {largest}"}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
