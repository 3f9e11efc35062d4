"""Pallas TPU kernels for the hot-embedding hash cache.

``probe_gather_pool`` is the serving fast path: the hash **probe** (linear
window over the open-addressing table) yields each lookup's slot and the
**miss mask** that feeds the tiered miss path; the masked row **gather** and
the per-bag **pooling** then run in one Pallas kernel
(kernels.embedding_bag), so the cached rows never round-trip through HBM
between those stages.  The probe is a fixed-width vectorized compare that
XLA fuses on its own; the kernel needs its result as DMA addresses, which
is why the slots ride into SMEM instead of being computed there.

``scatter_update`` is the swap-in kernel: it writes admitted rows into
their slots of the HBM-resident cache in place (input/output aliasing).
Each write is a read-modify-write of the slot's 128-lane line — a DMA can
move whole lines only, and neighbouring slots share a line when D < 128 —
done one row after another, so duplicates resolve last-write-wins: the
device side of the §3.1.1 cache swap-in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hotcache.table import HashCacheState, probe
from repro.kernels.embedding_bag import ID_BLOCK, embedding_bag, rows_per_line


@functools.partial(
    jax.jit, static_argnames=("num_bags", "max_probes", "interpret")
)
def probe_gather_pool(
    cache: HashCacheState,
    ids: jax.Array,  # [N] int32 lookup ids, N = num_bags * nnz
    weights: jax.Array,  # [N] f32 (0.0 masks a slot; 1/count for mean)
    num_bags: int,
    max_probes: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Probe + gather + pool: -> (pooled [num_bags, D] f32, miss [N] bool)."""
    C = cache.num_slots
    if C & (C - 1):
        raise ValueError("num_slots must be a power of two")
    slot, hit = probe(cache.keys, ids.astype(jnp.int32), max_probes)
    pooled = embedding_bag(
        cache.rows, slot, jnp.where(hit, weights.astype(jnp.float32), 0.0),
        num_bags, dim=cache.dim, interpret=interpret,
    )
    return pooled, ~hit


def _scatter_kernel(slot_ref, row_ref, val_in_ref, val_ref, line, sem, *, dim):
    del val_in_ref  # aliased with val_ref
    width = line.shape[1]
    pack = width // dim
    group = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // dim

    def body(i, carry):
        s = slot_ref[i]

        @pl.when(s >= 0)
        def _write():
            dst = val_ref.at[pl.ds(s // pack, 1)]
            read = pltpu.make_async_copy(dst, line, sem)
            read.start()
            read.wait()
            line[...] = jnp.where(
                group == s % pack, row_ref[pl.ds(i, 1), :], line[...]
            )
            write = pltpu.make_async_copy(line, dst, sem)
            write.start()
            write.wait()

        return carry

    jax.lax.fori_loop(0, slot_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter_update(
    values: jax.Array,  # [C/p, p*D] line-packed cache rows (donated, in place)
    slots: jax.Array,  # [K] int32 target slots (duplicates: last write wins)
    rows: jax.Array,  # [K, D] admitted rows
    interpret: bool = False,
) -> jax.Array:
    """Swap-in: write rows[i] into slot slots[i] of values, in place."""
    K, D = rows.shape
    width = values.shape[1]
    if width != D * rows_per_line(D):
        raise ValueError(f"values width {width} is not a line of {D}-wide rows")
    k_pad = -(-K // ID_BLOCK) * ID_BLOCK
    slots = jnp.pad(slots.astype(jnp.int32), (0, k_pad - K), constant_values=-1)
    # Each row repeated across its line's lane groups; the kernel keeps the
    # group of its slot.
    lines = jnp.pad(jnp.tile(rows.astype(values.dtype), (1, width // D)),
                    ((0, k_pad - K), (0, 0)))
    return pl.pallas_call(
        functools.partial(_scatter_kernel, dim=D),
        grid=(k_pad // ID_BLOCK,),
        in_specs=[
            pl.BlockSpec((ID_BLOCK,), lambda t: (t,), memory_space=pltpu.SMEM),
            pl.BlockSpec((ID_BLOCK, width), lambda t: (t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(values.shape, values.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, width), values.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        # operand order: (slots, lines, values); values aliases the output.
        input_output_aliases={2: 0},
        interpret=interpret,
    )(slots, lines, values)
