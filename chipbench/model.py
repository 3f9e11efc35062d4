"""The benchmark's own model: what every head shares, and the hand-off to
the configuration's head.

Nothing here imports the program.  A model head is a file of its own,
``chipbench/heads/<arch>.py``, found by the configuration's ``arch``
(``cells.load_head``); ``make_weights``, ``reference_scores``,
``dense_flops_per_request`` and ``top_in`` below hand the call to it.  A
head makes its weights on the device in one jitted call from the seed, in
the layout the served path takes (``{"emb": {"table"}, ...}``).  The
embedding table is made line-packed, ``[rows * dim / 128, 128]``: a line
holds ``128 // dim`` consecutive rows, so its row-major host copy reshaped
to ``[rows, dim]`` is the table, and the reference gathers whole lines
without a lane-padded copy of the table (``pool``).

Every head's reference pools with ``pool`` and multiplies with
``matmul``, so that all heads round alike.  Its matmuls run at float32
(``highest``); ``high`` computes each product from bfloat16 halves, three
passes, as a TPU does at that precision: that is the control.
"""
from __future__ import annotations

import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import cells

LANES = 128

# The MLPs come from this fixed seed, the table from the run's: the served
# dense stage compiles its weights in as constants, so MLPs that changed
# with the seed would make every run compile it again in set-up.
DENSE_SEED = 0


def total_rows(config: dict) -> int:
    """Rows of the fused table as the served path lays it out: field
    tables end to end, padded to a multiple of 8 rows per memory server."""
    raw = sum(t["rows"] for t in config["tables"])
    step = 8 * config["serve"]["num_servers"]
    return -(-max(raw, 1) // step) * step


def field_offsets(config: dict) -> np.ndarray:
    rows = [t["rows"] for t in config["tables"]]
    return np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int64)


def base_key(seed: int) -> jax.Array:
    """A key that depends on every bit of ``seed`` (up to 64)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _mlp_params(key, sizes) -> dict:
    out = {}
    for i in range(len(sizes) - 1):
        key, kw, kb = jax.random.split(key, 3)
        a = 1.0 / math.sqrt(sizes[i])
        out[f"w{i}"] = jax.random.uniform(kw, (sizes[i], sizes[i + 1]),
                                          jnp.float32, -a, a)
        out[f"b{i}"] = jax.random.uniform(kb, (sizes[i + 1],), jnp.float32,
                                          -a, a)
    return out


def _split_bf16(x):
    # reduce_precision, not a round trip through bfloat16: XLA may drop
    # a convert pair as excess precision, and did on the TPU.
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def matmul(a, b, precision: str):
    """``a @ b`` at float32 (``highest``), or from bfloat16 halves in three
    exact passes (``high``: hi*hi + hi*lo + lo*hi), the same on every
    backend."""
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(a, b, precision=hp)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return (jnp.matmul(ah, bh, precision=hp) + jnp.matmul(ah, bl, precision=hp)
            + jnp.matmul(al, bh, precision=hp))


def _mlp(params: dict, x, precision: str, final_act: bool):
    n = len(params) // 2
    for i in range(n):
        x = matmul(x, params[f"w{i}"], precision) + params[f"b{i}"]
        if i < n - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def pool(table, rows, mask, dim: int):
    """Sum-pooled bags, ``[B, F, dim]``, from the line-packed ``table``:
    ``rows`` ``[B, F, K]`` are fused-table rows, ``mask`` marks the ids
    each bag holds."""
    per = LANES // dim
    b, f, k = rows.shape
    lines = jnp.take(table, rows // per, axis=0)  # [B, F, K, 128]
    lines = lines.reshape(b, f, k, per, dim)
    pick = (rows % per)[..., None, None]
    vals = jnp.take_along_axis(lines, pick, axis=3)[..., 0, :]
    return jnp.sum(jnp.where(mask[..., None], vals, 0.0), axis=2)


def in_blocks(fn, config: dict, indices: np.ndarray, mask: np.ndarray,
              dense: np.ndarray, block: int) -> np.ndarray:
    """``fn(rows, mask, dense)`` over every request, ``block`` at a time,
    the last block padded (one shape, one compile); ``rows`` are the
    per-field ids offset into the fused table."""
    rows = indices.astype(np.int64) + field_offsets(config)[None, :, None]
    rows = rows.astype(np.int32)
    n = len(rows)
    out = np.empty(n, np.float32)
    for s in range(0, n, block):
        e = min(n, s + block)
        pad = block - (e - s)

        def blk(x):
            x = x[s:e]
            return np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)]
                                  ) if pad else x
        out[s:e] = np.asarray(fn(blk(rows), blk(mask), blk(dense)))[:e - s]
    return out


def score_gap(served: np.ndarray, ref: np.ndarray) -> float:
    """Widest score difference over the RMS reference score."""
    rms = float(np.sqrt(np.mean(ref.astype(np.float64) ** 2)))
    return float(np.max(np.abs(served - ref))) / max(rms, 1e-30)


def head(config: dict, root: pathlib.Path = cells.ROOT):
    """The module of the configuration's head (``cells.load_head``)."""
    return cells.load_head(config["arch"], root)


def make_weights(config: dict, seed: int, root: pathlib.Path = cells.ROOT
                 ) -> dict:
    """The served layout's weights, on the default device, from ``seed``."""
    return head(config, root).make_weights(config, seed)


def reference_scores(config: dict, weights: dict, indices: np.ndarray,
                     mask: np.ndarray, dense: np.ndarray,
                     precision: str = "highest", block: int = 4096,
                     root: pathlib.Path = cells.ROOT) -> np.ndarray:
    """Scores of the plain reference for every request, ``block`` at a
    time."""
    return head(config, root).reference_scores(
        config, weights, indices, mask, dense, precision, block)


def dense_flops_per_request(config: dict,
                            root: pathlib.Path = cells.ROOT) -> int:
    """Operations the dense stage needs per request (2 per multiply-add)."""
    return head(config, root).dense_flops_per_request(config)


def top_in(config: dict, root: pathlib.Path = cells.ROOT) -> int:
    """Width of the head's top MLP input."""
    return head(config, root).top_in(config)
