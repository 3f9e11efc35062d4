"""DLRM with the dot interaction, as published (Naumov et al. 2019,
facebookresearch/dlrm).

Sum-pooled bags, a bottom MLP with ReLU on every layer, pairwise dot
interaction over the bottom vector and the pooled fields (self-dots kept
when the configuration says so), the interaction concatenated with the
bottom vector, and a top MLP with ReLU on all but its last layer, whose
output is the score.

Weights: ``{"emb": {"table"}, "bottom": {w0, b0, ...}, "top": {...}}``,
the table from the run's seed, the MLPs from ``model.DENSE_SEED``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import cells, model

KEYS = ("bottom_mlp", "top_mlp", "interaction_itself")


def check(cfg: dict) -> None:
    if cfg["bottom_mlp"][-1] != cfg["embed_dim"]:
        raise cells.SpecError("bottom MLP must end at embed_dim")
    if cfg["top_mlp"][-1] != 1:
        raise cells.SpecError("top MLP must end in one score")


def program_config(cfg: dict):
    """The ``RecsysConfig`` the served path is built with."""
    from repro.core.sharding import TableSpec
    from repro.models.recsys import RecsysConfig

    specs = tuple(TableSpec(t["name"], t["rows"], nnz=t["nnz"])
                  for t in cfg["tables"])
    return RecsysConfig(
        name=cfg["name"], arch="dlrm", tables=specs,
        embed_dim=cfg["embed_dim"], n_dense=cfg["n_dense"],
        bottom_mlp=tuple(cfg["bottom_mlp"]),
        mlp=tuple(cfg["top_mlp"][:-1]), mode="hierarchical")


def _pairs(cfg: dict) -> int:
    v = len(cfg["tables"]) + 1
    return v * (v + 1) // 2 if cfg["interaction_itself"] else v * (v - 1) // 2


def top_in(cfg: dict) -> int:
    return _pairs(cfg) + cfg["embed_dim"]


@functools.lru_cache(maxsize=None)
def _weights_fn(lines: int, dim: int, n_dense: int, bottom: tuple,
                top_sizes: tuple):
    def make(k_emb, k_dense):
        k_bot, k_top = jax.random.split(k_dense)
        a = 1.0 / math.sqrt(dim)
        table = jax.random.uniform(k_emb, (lines, model.LANES), jnp.float32,
                                   -a, a)
        return {"emb": {"table": table},
                "bottom": model._mlp_params(k_bot, (n_dense,) + bottom),
                "top": model._mlp_params(k_top, top_sizes)}
    return jax.jit(make)


def make_weights(cfg: dict, seed: int) -> dict:
    dim = cfg["embed_dim"]
    lines = model.total_rows(cfg) * dim // model.LANES
    fn = _weights_fn(lines, dim, cfg["n_dense"], tuple(cfg["bottom_mlp"]),
                     (top_in(cfg),) + tuple(cfg["top_mlp"]))
    return fn(model.base_key(seed), model.base_key(model.DENSE_SEED))


@functools.lru_cache(maxsize=None)
def _forward_fn(dim: int, itself: bool, precision: str):
    def fwd(table, bottom, top, rows, mask, dense):
        f = rows.shape[1]
        pooled = model.pool(table, rows, mask, dim)
        bot = model._mlp(bottom, dense, precision, final_act=True)
        vecs = jnp.concatenate([bot[:, None, :], pooled], axis=1)
        gram = model.matmul(vecs, jnp.swapaxes(vecs, 1, 2), precision)
        iu, ju = np.triu_indices(f + 1, 0 if itself else 1)
        inter = gram[:, iu, ju]
        return model._mlp(top, jnp.concatenate([inter, bot], axis=-1),
                          precision, final_act=False)[:, 0]
    return jax.jit(fwd)


def reference_scores(cfg: dict, weights: dict, indices: np.ndarray,
                     mask: np.ndarray, dense: np.ndarray, precision: str,
                     block: int) -> np.ndarray:
    fn = _forward_fn(cfg["embed_dim"], bool(cfg["interaction_itself"]),
                     precision)
    table, bottom, top = (weights["emb"]["table"], weights["bottom"],
                          weights["top"])
    return model.in_blocks(
        lambda rows, m, d: fn(table, bottom, top, rows, m, d),
        cfg, indices, mask, dense, block)


def dense_flops_per_request(cfg: dict) -> int:
    """The two MLPs and the pairwise dots the interaction keeps."""
    def mlp(sizes):
        return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return (mlp([cfg["n_dense"]] + list(cfg["bottom_mlp"]))
            + 2 * _pairs(cfg) * cfg["embed_dim"]
            + mlp([top_in(cfg)] + list(cfg["top_mlp"])))
