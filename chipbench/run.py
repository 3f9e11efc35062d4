"""The chip benchmark's one command.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Serves the cell's configuration through ``FlexEMRServer`` under its traffic
mix, open loop, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and ``checks`` last: each
number compared beside its limit.  Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu logs under /tmp unless told otherwise: keep them in the checkout.
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".chipbench" / "tpu_logs"))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import cells, harness

    cell = cells.resolve(args.workload)
    import repro.runtime.serving  # noqa: F401 - the system under test
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"no TPU: JAX found {devices[0].platform}")
        return 2
    if len(devices) < cell.chips:
        harness.log(f"cell {cell.name} needs {cell.chips} chips, JAX found "
                    f"{len(devices)}")
        return 2
    harness.use_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T_START, devices[:cell.chips])
    print(harness.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
