"""Pallas TPU kernel for the prefetcher's top-k-neighbor select.

``topk_neighbor_select`` is the device half of the co-occurrence query: the
miner gathers each trigger row's candidate-neighbor scores into a dense
[M, L] tile, and this kernel reduces every row to its k strongest
candidates (score + column index) in one VMEM-resident pass — the same
selection `cooccur.topk_select_np` does on the host and `ref.py` defines as
the oracle.  On the TPU serving path this runs on the swap-in stream right
next to hotcache.kernels.scatter_update, so neighbor selection never
round-trips candidate tiles through HBM.

Structure: grid = (M / 8,); each step owns an [8, L] tile of score rows
(one f32 sublane tile; M is padded with -inf rows).  Selection is an
unrolled-by-fori_loop iterative argmax per row with a `taken` mask — ties
break to the lowest column index, matching the oracle's stable descending
sort.  The per-step outputs land in [8, K] blocks, accumulated as values
and written once (no dynamic stores).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


_ROWS = 8  # score rows per grid step: one f32 sublane tile


def _topk_kernel(s_ref, vals_ref, idx_ref, *, k: int):
    scores = s_ref[...]  # [R, L]
    R, L = scores.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (R, L), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (R, k), 1)
    neg_inf = jnp.float32(-jnp.inf)

    # `taken` is carried as int32: Mosaic cannot carry a bool vector
    # through a loop.
    def body(j, carry):
        taken, vals, idxs = carry
        avail = jnp.where(taken != 0, neg_inf, scores)
        best = jnp.max(avail, axis=1, keepdims=True)
        # Lowest untaken column attaining the max — on an all--inf remainder
        # this still walks the columns in index order, like the stable sort.
        cand = (taken == 0) & (avail == best)
        pick = jnp.min(jnp.where(cand, col, jnp.int32(L)), axis=1, keepdims=True)
        taken = jnp.where(col == pick, 1, taken)
        vals = jnp.where(kcol == j, best, vals)
        idxs = jnp.where(kcol == j, pick, idxs)
        return taken, vals, idxs

    _, vals, idxs = jax.lax.fori_loop(
        0,
        k,
        body,
        (
            jnp.zeros((R, L), jnp.int32),
            jnp.zeros((R, k), jnp.float32),
            jnp.zeros((R, k), jnp.int32),
        ),
    )
    vals_ref[...] = vals
    idx_ref[...] = idxs


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_neighbor_select(
    scores: jax.Array,  # [M, L] f32 candidate scores (-inf = absent slot)
    k: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-row top-k: -> (values [M, k] f32, indices [M, k] int32).

    Bit-equal to ref.topk_neighbor_select_ref (ties to the lowest index).
    The candidate axis is padded to a lane multiple with -inf; pad columns
    sort after every real column, so indices always point into [0, L).
    """
    M, L = scores.shape
    if k > L:
        raise ValueError(f"k={k} exceeds candidate width {L}")
    Lp = _round_up(max(L, 128), 128)
    Mp = _round_up(max(M, 1), _ROWS)
    s = jnp.full((Mp, Lp), -jnp.inf, jnp.float32).at[:M, :L].set(
        scores.astype(jnp.float32)
    )
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k),
        grid=(Mp // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, Lp), lambda m: (m, 0))],
        out_specs=[
            pl.BlockSpec((_ROWS, k), lambda m: (m, 0)),
            pl.BlockSpec((_ROWS, k), lambda m: (m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, k), jnp.float32),
            jax.ShapeDtypeStruct((Mp, k), jnp.int32),
        ],
        interpret=interpret,
    )(s)
    return vals[:M], idx[:M]
