"""The benchmark's own DLRM: seeded weights, the plain reference, FLOPs.

Nothing here imports the program.  The weights are made on the device in
one jitted call from the seed, in the layout the served path takes
(``{"emb": {"table"}, "bottom": {w0, b0, ...}, "top": {...}}``).  The
embedding table is made line-packed, ``[rows * dim / 128, 128]``: a line
holds ``128 // dim`` consecutive rows, so its row-major host copy reshaped
to ``[rows, dim]`` is the table, and the reference gathers whole lines
without a lane-padded copy of the table.

The reference is DLRM as published (Naumov et al. 2019,
facebookresearch/dlrm): sum-pooled bags, a bottom MLP with ReLU on every
layer, pairwise dot interaction over the bottom vector and the pooled
fields (self-dots kept when the configuration says so), the interaction
concatenated with the bottom vector, and a top MLP with ReLU on all but
its last layer, whose output is the score.  Its matmuls run at float32
(``highest``); ``high`` computes each product from bfloat16 halves, three
passes, as a TPU does at that precision: that is the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128


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


def top_in(config: dict) -> int:
    v = len(config["tables"]) + 1
    pairs = v * (v + 1) // 2 if config["interaction_itself"] else v * (v - 1) // 2
    return pairs + config["embed_dim"]


# The MLPs come from this fixed seed, the table from the run's: the served
# dense stage compiles its weights in as constants, so MLPs that changed
# with the seed would make every run compile it again in set-up.
DENSE_SEED = 0


@functools.lru_cache(maxsize=None)
def _weights_fn(lines: int, dim: int, n_dense: int, bottom: tuple,
                top_sizes: tuple):
    def make(k_emb, k_dense):
        k_bot, k_top = jax.random.split(k_dense)
        a = 1.0 / math.sqrt(dim)
        table = jax.random.uniform(k_emb, (lines, LANES), jnp.float32, -a, a)
        return (table, _mlp_params(k_bot, (n_dense,) + bottom),
                _mlp_params(k_top, top_sizes))
    return jax.jit(make)


def make_weights(config: dict, seed: int):
    """(line-packed table, bottom MLP, top MLP), on the default device, in
    one jitted call: the table from ``seed``, the MLPs from
    ``DENSE_SEED``."""
    dim = config["embed_dim"]
    lines = total_rows(config) * dim // LANES
    fn = _weights_fn(lines, dim, config["n_dense"],
                     tuple(config["bottom_mlp"]),
                     (top_in(config),) + tuple(config["top_mlp"]))
    return fn(base_key(seed), base_key(DENSE_SEED))


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


@functools.lru_cache(maxsize=None)
def _forward_fn(dim: int, itself: bool, precision: str):
    per = LANES // dim

    def fwd(table, bottom, top, rows, mask, dense):
        b, f, k = rows.shape
        lines = jnp.take(table, rows // per, axis=0)  # [B, F, K, 128]
        lines = lines.reshape(b, f, k, per, dim)
        pick = (rows % per)[..., None, None]
        vals = jnp.take_along_axis(lines, pick, axis=3)[..., 0, :]
        pooled = jnp.sum(jnp.where(mask[..., None], vals, 0.0), axis=2)
        bot = _mlp(bottom, dense, precision, final_act=True)
        vecs = jnp.concatenate([bot[:, None, :], pooled], axis=1)
        gram = matmul(vecs, jnp.swapaxes(vecs, 1, 2), precision)
        iu, ju = np.triu_indices(f + 1, 0 if itself else 1)
        inter = gram[:, iu, ju]
        return _mlp(top, jnp.concatenate([inter, bot], axis=-1), precision,
                    final_act=False)[:, 0]
    return jax.jit(fwd)


def reference_scores(config: dict, weights, indices: np.ndarray,
                     mask: np.ndarray, dense: np.ndarray,
                     precision: str = "highest", block: int = 4096
                     ) -> np.ndarray:
    """Scores of the plain DLRM for every request, ``block`` at a time."""
    table, bottom, top = weights
    fn = _forward_fn(config["embed_dim"], bool(config["interaction_itself"]),
                     precision)
    rows = indices.astype(np.int64) + field_offsets(config)[None, :, None]
    rows = rows.astype(np.int32)
    n = len(rows)
    out = np.empty(n, np.float32)
    for s in range(0, n, block):
        e = min(n, s + block)
        pad = block - (e - s)  # one shape, one compile

        def blk(x):
            x = x[s:e]
            return np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)]
                                  ) if pad else x
        out[s:e] = np.asarray(fn(table, bottom, top, blk(rows), blk(mask),
                                 blk(dense)))[:e - s]
    return out


def score_gap(served: np.ndarray, ref: np.ndarray) -> float:
    """Widest score difference over the RMS reference score."""
    rms = float(np.sqrt(np.mean(ref.astype(np.float64) ** 2)))
    return float(np.max(np.abs(served - ref))) / max(rms, 1e-30)


def dense_flops_per_request(config: dict) -> int:
    """Operations the dense stage needs per request (2 per multiply-add):
    the two MLPs and the pairwise dots the interaction keeps."""
    def mlp(sizes):
        return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))
    v = len(config["tables"]) + 1
    pairs = v * (v + 1) // 2 if config["interaction_itself"] else v * (v - 1) // 2
    return (mlp([config["n_dense"]] + list(config["bottom_mlp"]))
            + 2 * pairs * config["embed_dim"]
            + mlp([top_in(config)] + list(config["top_mlp"])))
