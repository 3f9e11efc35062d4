"""The comparison's control: the reference at the precision just below the
configuration's, put in the program's place, read at a cell's own size.

  python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed: the weights and the window's requests that a run of the
cell with that seed makes, scored by the reference at ``highest``
(float32) and at ``high`` (bfloat16 halves, three passes, the same on
every backend); prints the gap as a run measures it (``score_gap``:
widest score difference over the RMS score) beside the cell's limit.  A
sound limit lies below every reading.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402


def control_gap(config: dict, mix: dict, seed: int, seconds: float) -> float:
    """``score_gap`` of the reference at ``high`` against the reference at
    ``highest``, on the window of a run of the cell with ``seed``."""
    from chipbench import model, traffic

    warm = float(mix["warm_s"])
    tr = traffic.make_traffic(seed, config, mix,
                              warm + seconds + float(mix["tail_s"]))
    w = (tr.t >= warm) & (tr.t < warm + seconds)
    weights = model.make_weights(config, seed)
    args = (tr.indices[w], tr.mask[w], tr.dense[w])
    ref = model.reference_scores(config, weights, *args)
    low = model.reference_scores(config, weights, *args, precision="high")
    return model.score_gap(low, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from chipbench import cells

    cell = cells.resolve(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: JAX found {jax.devices()[0].platform}",
              file=sys.stderr)
        return 2
    limit = cell.config["correctness"]["score_gap_limit"]
    for seed in (int(s) for s in args.seeds.split(",")):
        gap = control_gap(cell.config, cell.traffic, seed, args.seconds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_score_gap": gap, "limit": limit,
                          "control_fails": limit is None or gap > limit}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
