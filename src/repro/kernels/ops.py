"""Wrappers that pick between the Pallas kernels and their jnp references.

By default (`use_pallas=False`) every call runs the pure-jnp reference from
ref.py, on any backend — nothing here checks for a TPU.  `use_pallas=True`
runs the compiled kernel (TPU only); `interpret=True` runs the kernel body
through the Pallas interpreter, on any backend (what the CPU tests sweep).
The served path calls no kernel through this module.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.dot_interaction import dot_interaction as _dot_pallas
from repro.kernels.embedding_bag import embedding_bag as _bag_pallas
from repro.kernels.embedding_bag import pack_rows
from repro.kernels.flash_attention import flash_attention as _flash_pallas


def embedding_bag(
    table, indices, weights, num_bags, *, use_pallas=False, interpret=False
):
    if use_pallas or interpret:
        # The kernel reads line-packed rows; packing here relayouts the
        # table on every call, which a caller on a hot path avoids by
        # storing it packed and calling the kernel directly.
        return _bag_pallas(
            pack_rows(table), indices, weights, num_bags,
            dim=table.shape[1], interpret=interpret,
        )
    return ref.embedding_bag_ref(table, indices, weights, num_bags)


def bag_lookup(
    table: jax.Array,
    indices: jax.Array,  # [B, F, nnz]
    mask: jax.Array,  # [B, F, nnz]
    *,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """[B,F,nnz] multi-hot lookup -> [B,F,D] sum-pooled, via the fused kernel."""
    B, F, nnz = indices.shape
    flat_idx = indices.reshape(-1).astype(jnp.int32)
    flat_w = mask.reshape(-1).astype(jnp.float32)
    out = embedding_bag(
        table, flat_idx, flat_w, B * F, use_pallas=use_pallas, interpret=interpret
    )
    return out.reshape(B, F, table.shape[1])


def dot_interaction_triu(
    x: jax.Array, *, use_pallas: bool = False, interpret: bool = False
) -> jax.Array:
    """[B,F,D] -> [B, F*(F+1)/2] upper-triangle (incl. diag) pairwise dots."""
    if use_pallas or interpret:
        prods = _dot_pallas(x, interpret=interpret)
    else:
        prods = ref.dot_interaction_ref(x)
    F = x.shape[1]
    iu, ju = np.triu_indices(F)
    return prods[:, iu, ju]


def flash_attention(
    q, k, v, *, causal=True, block_q=256, block_k=256,
    use_pallas=False, interpret=False,
):
    if use_pallas or interpret:
        return _flash_pallas(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    return ref.flash_attention_ref(q, k, v, causal=causal)
