"""Fused embedding-bag (gather + pool) Pallas TPU kernel — the paper's hot path.

Table layout.  The kernel reads a table whose rows are packed into 128-lane
*lines*: ``[ceil(V / p), p * D]`` with ``p = 128 // D`` rows per line when
``D`` divides 128, and one row per line when ``D`` is a multiple of 128.
The TPU stores a plain ``f32[V, 64]`` array with V as the minor (lane)
dimension, so a row is not contiguous there and any row DMA would make XLA
relayout the whole table (twice its bytes once padded to 128 lanes).  A
line-packed table is row-contiguous as stored: ``pack_rows`` builds it once,
where the table is created, and no call relayouts it.

TPU-native structure: the grid walks tiles of ``TN`` lookup slots.  Row ids
and weights ride in SMEM as 1-D blocks (1024-aligned, matching XLA's tiling
of 1-D arrays); the kernel issues one line DMA per slot from the HBM table
into a VMEM buffer, waits for the tile, then pools each bag in VMEM —
selecting the slot's lanes inside its line and scaling by its weight — so a
bag's rows never round-trip through HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# XLA tiles 1-D int32/f32 arrays by 1024 elements; SMEM blocks must match.
ID_BLOCK = 1024


def rows_per_line(dim: int) -> int:
    """Rows packed into one 128-lane line for embedding width `dim`."""
    if dim % LANES == 0:
        return 1
    if LANES % dim:
        raise ValueError(f"row width {dim} must divide or be a multiple of {LANES}")
    return LANES // dim


def pack_rows(rows: jax.Array) -> jax.Array:
    """[V, D] rows -> [ceil(V / p), p * D] lines (zero-padded tail)."""
    V, D = rows.shape
    p = rows_per_line(D)
    pad = (-V) % p
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    return rows.reshape(-1, p * D)


def unpack_rows(lines: jax.Array, dim: int) -> jax.Array:
    """Inverse of pack_rows (padded tail rows included)."""
    return lines.reshape(-1, dim)


def take_rows(lines: jax.Array, row_ids: jax.Array, dim: int) -> jax.Array:
    """jnp gather of rows [..., D] from a line-packed table."""
    p = lines.shape[1] // dim
    line = jnp.take(lines, row_ids // p, axis=0)  # [..., p * D]
    line = line.reshape(line.shape[:-1] + (p, dim))
    sub = (row_ids % p)[..., None, None]
    return jnp.take_along_axis(line, sub, axis=-2)[..., 0, :]


def _bag_kernel(idx_ref, w_ref, lines_hbm, out_ref, buf, sem, *, nnz, dim):
    tn, width = buf.shape
    pack = width // dim

    def start(k, carry):
        pltpu.make_async_copy(
            lines_hbm.at[pl.ds(idx_ref[k] // pack, 1)], buf.at[pl.ds(k, 1)], sem
        ).start()
        return carry

    def wait(k, carry):
        pltpu.make_async_copy(
            lines_hbm.at[pl.ds(0, 1)], buf.at[pl.ds(0, 1)], sem
        ).wait()
        return carry

    jax.lax.fori_loop(0, tn, start, 0)
    jax.lax.fori_loop(0, tn, wait, 0)
    group = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // dim

    def pool(b, carry):
        acc = jnp.zeros((1, width), jnp.float32)
        for j in range(nnz):
            k = b * nnz + j
            line = buf[pl.ds(k, 1), :].astype(jnp.float32)
            if pack > 1:
                line = jnp.where(group == idx_ref[k] % pack, line, 0.0)
            acc = acc + line * w_ref[k]
        row = acc[:, :dim]
        for g in range(1, pack):
            row = row + acc[:, g * dim:(g + 1) * dim]
        out_ref[pl.ds(b, 1), :] = row
        return carry

    jax.lax.fori_loop(0, tn // nnz, pool, 0)


@functools.partial(
    jax.jit, static_argnames=("num_bags", "dim", "interpret")
)
def embedding_bag(
    table: jax.Array,  # [R, p * D] line-packed rows (pack_rows); [V, D] if p = 1
    indices: jax.Array,  # [N] int32 row ids, N = num_bags * nnz
    weights: jax.Array,  # [N] f32 (0.0 masks a slot)
    num_bags: int,
    dim: int | None = None,  # row width D; defaults to the line width
    interpret: bool = False,
) -> jax.Array:
    """[num_bags, D] f32 weighted sums over fixed-nnz bags of table rows."""
    N = indices.shape[0]
    R, width = table.shape
    dim = width if dim is None else dim
    if N % num_bags:
        raise ValueError("fixed-nnz layout required: N % num_bags != 0")
    if width != dim * rows_per_line(dim):
        raise ValueError(f"table width {width} is not a line of {dim}-wide rows")
    nnz = N // num_bags
    pack = width // dim
    # One tile: a multiple of the SMEM block and of 8 whole bags (sublanes).
    tn = math.lcm(ID_BLOCK, 8 * nnz)
    n_pad = -(-N // tn) * tn
    idx = jnp.clip(indices.astype(jnp.int32), 0, R * pack - 1)
    idx = jnp.pad(idx, (0, n_pad - N))
    w = jnp.pad(weights.astype(jnp.float32), (0, n_pad - N))
    tb = tn // nnz
    out = pl.pallas_call(
        functools.partial(_bag_kernel, nnz=nnz, dim=dim),
        grid=(n_pad // tn,),
        in_specs=[
            pl.BlockSpec((tn,), lambda t: (t,), memory_space=pltpu.SMEM),
            pl.BlockSpec((tn,), lambda t: (t,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tb, dim), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad // nnz, dim), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tn, width), table.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )(idx, w, table)
    return out[:num_bags]
