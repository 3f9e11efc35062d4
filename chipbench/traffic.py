"""Seeded open-loop traffic, made whole before the run starts.

Arrivals: a Poisson process conditioned on its count, so every seed offers
exactly ``rate * duration`` requests (the same work), at sorted uniform
times.  Payloads: one vectorised draw per field for the whole run.  Key
laws:

* ``zipf``: rank ``r`` drawn with probability proportional to
  ``(r + 1) ** -alpha`` by the inverse CDF (small ids are the hot ones, the
  rank-ordered layout of ``repro.data.synthetic.zipf_indices``, copied
  here), independently per id.
* ``uniform``: ids drawn with ``rng.integers(0, rows)``.

Every bag holds its field's ``nnz`` ids: the configuration's pooling
factor, fixed, as the DLRM reference generator's
``--num-indices-per-lookup-fixed`` holds it.  Dense features are uniform
on [0, 1), as that generator's random inputs are (facebookresearch/dlrm,
``generate_uniform_input_batch``); their values set no work.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Traffic:
    t: np.ndarray  # intended arrival, seconds from the stream's start
    indices: np.ndarray  # [n, F, nnz] int32, per-field row ids
    mask: np.ndarray  # [n, F, nnz] bool
    dense: np.ndarray  # [n, n_dense] float32

    def __len__(self) -> int:
        return len(self.t)

    def payloads(self) -> list[dict]:
        """One request dict per arrival (views into the arrays)."""
        return [{"indices": i, "mask": m, "dense": d}
                for i, m, d in zip(self.indices, self.mask, self.dense)]


def streams(seed: int, n: int) -> list[np.random.Generator]:
    """``n`` independent generators from one seed of any size."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    return [np.random.default_rng(s) for s in ss.spawn(n)]


def zipf_ranks(rng: np.random.Generator, rows: int, size, alpha: float
               ) -> np.ndarray:
    u = rng.random(size)
    if alpha <= 1.0 + 1e-6:
        ranks = np.exp(u * np.log(rows)) - 1.0
    else:
        a1 = 1.0 - alpha
        ranks = (u * (rows ** a1 - 1.0) + 1.0) ** (1.0 / a1) - 1.0
    return np.clip(ranks.astype(np.int64), 0, rows - 1)


def arrival_times(rng: np.random.Generator, rate: float, duration: float
                  ) -> np.ndarray:
    n = int(round(rate * duration))
    return np.sort(rng.uniform(0.0, duration, n))


def field_ids(rng: np.random.Generator, rows: int, nnz: int, n: int,
              mix: dict) -> np.ndarray:
    """[n, nnz] ids of one field under the mix's key law."""
    if mix["key_law"] == "uniform":
        return rng.integers(0, rows, (n, nnz))
    return zipf_ranks(rng, rows, (n, nnz), mix["alpha"])


def make_traffic(seed: int, config: dict, mix: dict, duration: float,
                 rate: float | None = None) -> Traffic:
    """Every request of a run of ``duration`` seconds at ``rate`` (default:
    the mix's), from ``seed``."""
    r_arr, r_ids, r_dense = streams(seed, 3)
    t = arrival_times(r_arr, mix["rate_rps"] if rate is None else rate,
                      duration)
    n = len(t)
    tables = config["tables"]
    nnz = max(tb["nnz"] for tb in tables)
    indices = np.zeros((n, len(tables), nnz), np.int32)
    mask = np.zeros((n, len(tables), nnz), bool)
    for f, tb in enumerate(tables):
        k = tb["nnz"]
        indices[:, f, :k] = field_ids(r_ids, tb["rows"], k, n, mix)
        mask[:, f, :k] = True
    dense = r_dense.random((n, config["n_dense"]), np.float32)
    return Traffic(t, indices, mask, dense)
