"""DisaggEmbedding — FlexEMR's disaggregated embedding layer on a TPU mesh.

The fused embedding table plays the role of the paper's *embedding servers*
(row-range shards on the `model` mesh axis own disjoint row ranges, exactly the
range routing table of core.sharding).  The dense-compute side of the mesh
plays the *ranker*.  Three lookup paths are provided; they are numerically
identical (tests enforce allclose against a single-device oracle) but move very
different byte counts over the interconnect — which is the paper's entire
subject:

``mode="baseline"``      Fig 4(a): every shard contributes the *raw rows* it
                         owns; the row-level ``[B, F, nnz, D]`` tensor crosses
                         the network (one psum) and the ranker pools it.

``mode="hierarchical"``  Fig 4(b): every shard pools its own rows first
                         (*pooling pushdown* onto the embedding server), and
                         only ``[B, F, D]`` partials cross the network — an
                         ``nnz``-fold reduction in collective bytes.

Adaptive caching (§3.1.1) appears in two TPU-native forms:
  * **row-level hot cache** — hot hits resolve locally and are added after
    the cold psum.  Zero interconnect bytes for hot rows on the baseline
    path; on the hierarchical path it removes HBM gather traffic from the
    big shard.  Two cache data structures are accepted: the legacy flat
    sorted ``(ids, rows)`` slab (binary search) and the repro.hotcache
    ``HashCacheState`` — an open-addressing hash table with LFU
    admission/eviction whose probe+gather+pool fuses into one Pallas kernel
    on TPU (repro.hotcache.kernels).
  * **field-level replication** — fields whose entire vocab fits the cache
    budget are replicated outright and never enter the collective, shrinking
    the psum payload *statically* (visible in compiled HLO).  The adaptive
    controller (core.adaptive_cache) picks which fields/rows, trading cache
    bytes against activation memory exactly like the paper's GPU-memory model.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.hotcache.table import (
    HashCacheState,
    cache_insert as hc_insert,
    cache_lookup as hc_lookup,
    cache_partition_spec,
)
from repro.core.sharding import (
    AXIS_DATA,
    AXIS_MODEL,
    AXIS_POD,
    FusedTables,
    TableSpec,
    make_fused_tables,
)

Pooling = str  # 'sum' | 'mean'


ROW_ID_PAD = np.iinfo(np.int32).max  # fused row ids are < 2^31 for all configs


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HotCacheState:
    """Replicated hot-row cache (paper §3.1.1). ids are sorted fused row ids."""

    ids: jax.Array  # [K] int32, sorted ascending, padded with ROW_ID_PAD
    rows: jax.Array  # [K, D]

    @property
    def capacity(self) -> int:
        return int(self.ids.shape[0])


def empty_cache(capacity: int, dim: int, dtype=jnp.float32) -> HotCacheState:
    return HotCacheState(
        ids=jnp.full((capacity,), ROW_ID_PAD, dtype=jnp.int32),
        rows=jnp.zeros((capacity, dim), dtype=dtype),
    )


@dataclasses.dataclass
class DisaggEmbedding:
    """Sharded, cached, pooling-pushdown embedding bag.

    Args:
      specs: one TableSpec per sparse field (order defines the F axis).
      dim: embedding dim (shared — fused-table requirement).
      num_shards: number of embedding servers == size of the `model` axis.
      mode: 'baseline' | 'hierarchical' (see module docstring).
      replicated_fields: indices into `specs` replicated on every chip.
      comm_dtype: optional dtype for the cross-shard partials (beyond-paper
        compression knob; None = keep param dtype).
      param_dtype: table storage dtype.
    """

    specs: Sequence[TableSpec]
    dim: int
    num_shards: int
    mode: str = "hierarchical"
    replicated_fields: tuple[int, ...] = ()
    comm_dtype: jnp.dtype | None = None
    param_dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        if self.mode not in ("baseline", "hierarchical", "mesh2d"):
            raise ValueError(f"unknown lookup mode {self.mode!r}")
        self.specs = tuple(self.specs)
        rep = set(self.replicated_fields)
        if not rep.issubset(range(len(self.specs))):
            raise ValueError("replicated_fields out of range")
        self.sharded_idx = tuple(
            i for i in range(len(self.specs)) if i not in rep
        )
        self.replicated_idx = tuple(sorted(rep))
        self.sharded: FusedTables | None = (
            make_fused_tables(
                [self.specs[i] for i in self.sharded_idx], self.dim, self.num_shards
            )
            if self.sharded_idx
            else None
        )
        self.replicated: FusedTables | None = (
            make_fused_tables(
                [self.specs[i] for i in self.replicated_idx], self.dim, 1
            )
            if self.replicated_idx
            else None
        )
        # Static per-field pooling selector and output permutation.
        order = list(self.sharded_idx) + list(self.replicated_idx)
        self._inv_perm = np.argsort(np.asarray(order))  # group-order -> F order
        self._mean_mask = np.asarray(
            [s.pooling == "mean" for s in self.specs], dtype=bool
        )

    # ------------------------------------------------------------------ params

    @property
    def num_fields(self) -> int:
        return len(self.specs)

    def init(self, key: jax.Array, scale: float = 0.01) -> dict:
        params = {}
        if self.sharded is not None:
            k1, key = jax.random.split(key)
            params["table"] = (
                jax.random.normal(
                    k1, (self.sharded.total_rows, self.dim), self.param_dtype
                )
                * scale
            )
        if self.replicated is not None:
            k2, key = jax.random.split(key)
            params["rep_table"] = (
                jax.random.normal(
                    k2, (self.replicated.total_rows, self.dim), self.param_dtype
                )
                * scale
            )
        return params

    def param_specs(self, batch_axes=(AXIS_DATA,)) -> dict:
        """PartitionSpecs: fused table row-sharded on `model` (paper layout)
        or over the whole mesh (`mesh2d`, the beyond-paper layout where every
        row exists exactly once -> embedding gradients stay shard-local)."""
        specs = {}
        if self.sharded is not None:
            if self.mode == "mesh2d":
                specs["table"] = P(tuple(batch_axes) + (AXIS_MODEL,), None)
            else:
                specs["table"] = P(AXIS_MODEL, None)
        if self.replicated is not None:
            specs["rep_table"] = P(None, None)
        return specs

    def abstract_params(self) -> dict:
        out = {}
        if self.sharded is not None:
            out["table"] = jax.ShapeDtypeStruct(
                (self.sharded.total_rows, self.dim), self.param_dtype
            )
        if self.replicated is not None:
            out["rep_table"] = jax.ShapeDtypeStruct(
                (self.replicated.total_rows, self.dim), self.param_dtype
            )
        return out

    # ------------------------------------------------------------- local math

    def _fused_rows(self, tables: FusedTables, idx_group: jax.Array, local_fields) -> jax.Array:
        """Per-field indices -> fused global row ids. idx_group: [B, Fg, nnz]."""
        offs = jnp.asarray(tables.field_offsets_array().astype(np.int32))  # [Fg]
        return idx_group.astype(jnp.int32) + offs[None, :, None]

    @staticmethod
    def _gather_masked(table: jax.Array, local: jax.Array, hit: jax.Array) -> jax.Array:
        """Gather rows for in-range hits; zeros elsewhere. local: [B,Fg,nnz]."""
        rows = jnp.take(
            table, jnp.clip(local, 0, table.shape[0] - 1), axis=0
        )  # [B,Fg,nnz,D]
        return jnp.where(hit[..., None], rows, jnp.zeros((), rows.dtype))

    def _pool(self, summed: jax.Array, counts: jax.Array, field_ids) -> jax.Array:
        """Apply per-field sum/mean. summed [B,Fg,D], counts [B,Fg]."""
        mean_mask = jnp.asarray(self._mean_mask[np.asarray(field_ids)])
        denom = jnp.maximum(counts, 1.0)[..., None]
        return jnp.where(mean_mask[None, :, None], summed / denom, summed)

    # ------------------------------------------------------- single-device ref

    def lookup_reference(self, params: dict, indices: jax.Array, mask: jax.Array) -> jax.Array:
        """Dense single-device oracle: plain gather + pool. [B,F,nnz] -> [B,F,D]."""
        out_groups = []
        field_groups = []
        for tables, key_, fields in (
            (self.sharded, "table", self.sharded_idx),
            (self.replicated, "rep_table", self.replicated_idx),
        ):
            if tables is None:
                continue
            idx_g = indices[:, np.asarray(fields), :]
            m_g = mask[:, np.asarray(fields), :]
            fused = self._fused_rows(tables, idx_g, fields)
            rows = self._gather_masked(params[key_], fused, m_g)
            summed = rows.sum(axis=2)
            counts = m_g.sum(axis=2).astype(summed.dtype)
            out_groups.append(self._pool(summed, counts, fields))
            field_groups.extend(fields)
        out = jnp.concatenate(out_groups, axis=1) if len(out_groups) > 1 else out_groups[0]
        return self._unpermute(out)

    def _unpermute(self, out: jax.Array) -> jax.Array:
        if np.array_equal(self._inv_perm, np.arange(self.num_fields)):
            return out
        return out[:, jnp.asarray(self._inv_perm), :]

    # --------------------------------------------------------- sharded lookup

    def _shard_local(
        self,
        table_shard: jax.Array,
        idx_g: jax.Array,
        m_g: jax.Array,
        cache: HotCacheState | None,
        offsets: np.ndarray,
    ):
        """Per-shard compute for (a chunk of) the sharded field group.

        `offsets` are the parent fused-table row offsets of the chunk's
        fields, so chunked lookups keep the parent routing geometry.
        Returns (to_psum, local_add, counts):
          to_psum   — tensor that must cross the network (mode-dependent rank),
          local_add — hot-cache contribution (already pooled, replicated),
          counts    — per-(B,Fg) valid counts (for mean pooling).
        """
        tables = self.sharded
        assert tables is not None
        shard_id = jax.lax.axis_index(AXIS_MODEL)
        offs = jnp.asarray(offsets.astype(np.int32))
        fused = idx_g.astype(jnp.int32) + offs[None, :, None]  # [B,Fg,nnz]
        counts = m_g.sum(axis=2).astype(table_shard.dtype)

        hot = None
        if isinstance(cache, HashCacheState):
            if cache.num_slots > 0:
                # hotcache fast path: open-addressing probe (repro.hotcache);
                # on TPU the Pallas kernel fuses this probe with the pool.
                query = jnp.where(m_g, fused, ROW_ID_PAD)
                hot_rows, is_hot = hc_lookup(cache, query)
                hot_rows = jnp.where(
                    is_hot[..., None], hot_rows.astype(table_shard.dtype), 0
                )
                hot = hot_rows.sum(axis=2)  # [B,Fg,D] pooled hot contribution
                m_g = m_g & ~is_hot  # cold residue -> shard path
        elif cache is not None and cache.capacity > 0:
            pos = jnp.searchsorted(cache.ids, fused)  # [B,Fg,nnz]
            pos_c = jnp.clip(pos, 0, cache.capacity - 1)
            is_hot = (jnp.take(cache.ids, pos_c) == fused) & m_g
            hot_rows = jnp.take(cache.rows, pos_c, axis=0).astype(table_shard.dtype)
            hot_rows = jnp.where(is_hot[..., None], hot_rows, 0)
            hot = hot_rows.sum(axis=2)  # [B,Fg,D] pooled hot contribution
            m_g = m_g & ~is_hot  # cold residue goes through the shard path

        local = fused - shard_id * tables.rows_per_shard
        hit = (local >= 0) & (local < tables.rows_per_shard) & m_g
        rows = self._gather_masked(table_shard, local, hit)  # [B,Fg,nnz,D]

        if self.mode == "baseline":
            to_psum = rows  # raw rows cross the network (fig 4a)
        else:
            to_psum = rows.sum(axis=2)  # pooled partials cross (fig 4b)
        if self.comm_dtype is not None:
            to_psum = to_psum.astype(self.comm_dtype)
        return to_psum, hot, counts

    def _combine(self, psummed: jax.Array, hot, counts, fields) -> jax.Array:
        """Ranker-side combine after the collective."""
        if self.mode == "baseline":
            summed = psummed.astype(jnp.float32).sum(axis=2)
        else:
            summed = psummed.astype(jnp.float32)
        if hot is not None:
            summed = summed + hot.astype(jnp.float32)
        return self._pool(summed, counts.astype(jnp.float32), fields)

    def lookup(
        self,
        params: dict,
        indices: jax.Array,
        mask: jax.Array,
        mesh: Mesh | None = None,
        cache: HotCacheState | None = None,
        batch_axes: tuple[str, ...] = (AXIS_DATA,),
        num_chunks: int = 1,
    ) -> jax.Array:
        """[B, F, nnz] int indices + bool mask -> [B, F, D] pooled embeddings.

        With a mesh: shard_map over (batch_axes ∪ model); without: oracle path.
        num_chunks > 1 splits the sharded fields into independent lookups whose
        collectives XLA can overlap with dense compute (§3.2 engine analogue).
        """
        if mesh is None:
            return self.lookup_reference(params, indices, mask)

        if self.mode == "mesh2d":
            return self._lookup_mesh2d(params, indices, mask, mesh, batch_axes)

        out_parts = {}
        if self.sharded is not None:
            fields = np.asarray(self.sharded_idx)
            all_offs = self.sharded.field_offsets_array()
            nchunk = max(1, min(num_chunks, len(fields)))
            splits = np.array_split(np.arange(len(fields)), nchunk)

            chunk_outs = []
            for pos in splits:
                if len(pos) == 0:
                    continue
                sub_fields = fields[pos]
                idx_g = indices[:, sub_fields, :]
                m_g = mask[:, sub_fields, :]
                offs = all_offs[pos]

                def sharded_fn(table_shard, idx_l, m_l, cache_l, offs=offs,
                               sub_fields=tuple(sub_fields)):
                    to_psum, hot, counts = self._shard_local(
                        table_shard, idx_l, m_l, cache_l, offs
                    )
                    psummed = jax.lax.psum(to_psum, AXIS_MODEL)
                    return self._combine(psummed, hot, counts, sub_fields)

                cache_in = cache if cache is not None else None
                args = (params["table"], idx_g, m_g, cache_in)
                if cache is None:
                    cache_spec = None
                elif isinstance(cache, HashCacheState):
                    cache_spec = cache_partition_spec(cache)
                else:
                    cache_spec = HotCacheState(ids=P(None), rows=P(None, None))
                in_specs = (
                    P(AXIS_MODEL, None),
                    P(batch_axes, None, None),
                    P(batch_axes, None, None),
                    cache_spec,
                )
                chunk_outs.append(
                    shard_map(
                        sharded_fn,
                        mesh=mesh,
                        in_specs=in_specs,
                        out_specs=P(batch_axes, None, None),
                        check_vma=False,
                    )(*args)
                )
            out_parts["sharded"] = (
                jnp.concatenate(chunk_outs, axis=1)
                if len(chunk_outs) > 1
                else chunk_outs[0]
            )

        if self.replicated is not None:
            fields = np.asarray(self.replicated_idx)
            idx_g = indices[:, fields, :]
            m_g = mask[:, fields, :]
            fused = self._fused_rows(self.replicated, idx_g, self.replicated_idx)
            rows = self._gather_masked(params["rep_table"], fused, m_g)
            summed = rows.sum(axis=2).astype(jnp.float32)
            counts = m_g.sum(axis=2).astype(jnp.float32)
            out_parts["replicated"] = self._pool(summed, counts, self.replicated_idx)

        groups = [v for v in (out_parts.get("sharded"), out_parts.get("replicated")) if v is not None]
        out = jnp.concatenate(groups, axis=1) if len(groups) > 1 else groups[0]
        return self._unpermute(out)

    def _lookup_mesh2d(
        self,
        params: dict,
        indices: jax.Array,
        mask: jax.Array,
        mesh: Mesh,
        batch_axes: tuple[str, ...],
    ) -> jax.Array:
        """Beyond-paper layout: rows sharded over the FULL mesh (every row
        exists once).  Indices (tiny, int32) are all-gathered across the data
        axes; every chip partially pools the rows it owns for the *global*
        batch; a chained psum-scatter delivers the pooled result already
        sharded over (batch_axes x model) — the dense-stage layout.

        Collective bytes per step: idx all-gather + [B,F,D] reduce-scatter
        (+ its all-gather transpose in backward); the table-sized DP gradient
        all-reduce of the paper layout disappears because gradients scatter
        into locally-owned rows only.
        """
        if self.replicated is not None:
            raise NotImplementedError("mesh2d: plain sharded fields only")
        tables = self.sharded
        all_axes = tuple(batch_axes) + (AXIS_MODEL,)
        offs = tables.field_offsets_array().astype(np.int32)

        def fn(table_shard, idx_l, m_l):
            # reconstruct the global batch's indices (inner axes first)
            for ax in reversed(batch_axes):
                idx_l = jax.lax.all_gather(idx_l, ax, axis=0, tiled=True)
                m_l = jax.lax.all_gather(m_l, ax, axis=0, tiled=True)
            shard_id = jnp.zeros((), jnp.int32)
            for ax in all_axes:
                shard_id = shard_id * mesh.shape[ax] + jax.lax.axis_index(ax)
            fused = idx_l.astype(jnp.int32) + jnp.asarray(offs)[None, :, None]
            local = fused - shard_id * tables.rows_per_shard
            hit = (local >= 0) & (local < tables.rows_per_shard) & m_l
            rows = self._gather_masked(table_shard, local, hit)
            partial = rows.sum(axis=2)  # [B_global, F, D] partial pools
            if self.comm_dtype is not None:
                partial = partial.astype(self.comm_dtype)
            counts = m_l.sum(axis=2).astype(jnp.float32)
            for ax in all_axes:  # outer-to-inner: matches P(all_axes) layout
                partial = jax.lax.psum_scatter(
                    partial, ax, scatter_dimension=0, tiled=True
                )
                counts = jax.lax.dynamic_slice_in_dim(
                    counts,
                    jax.lax.axis_index(ax) * (counts.shape[0] // mesh.shape[ax]),
                    counts.shape[0] // mesh.shape[ax],
                    axis=0,
                )
            return self._pool(
                partial.astype(jnp.float32), counts, self.sharded_idx
            )

        return shard_map(
            fn,
            mesh=mesh,
            in_specs=(
                P(all_axes, None),
                P(batch_axes, None, None),
                P(batch_axes, None, None),
            ),
            out_specs=P(all_axes, None, None),
            check_vma=False,
        )(params["table"], indices, mask)

    def lookup_rows(
        self,
        params: dict,
        indices: jax.Array,
        mask: jax.Array,
        mesh: Mesh | None = None,
        batch_axes: tuple[str, ...] = (AXIS_DATA,),
    ) -> jax.Array:
        """Unpooled lookup: [B, F, nnz] -> [B, F, nnz, D] raw rows (masked
        slots are zero).  This is inherently the fig-4(a) traffic pattern —
        row-level tensors cross the network — used by models that need
        per-item embeddings (sequence/interest models like MIND)."""
        if self.replicated is not None:
            raise NotImplementedError("lookup_rows with replicated fields")
        tables = self.sharded

        if mesh is None:
            fused = self._fused_rows(tables, indices, self.sharded_idx)
            return self._gather_masked(params["table"], fused, mask)

        def fn(table_shard, idx_l, m_l):
            shard_id = jax.lax.axis_index(AXIS_MODEL)
            offs = jnp.asarray(tables.field_offsets_array().astype(np.int32))
            fused = idx_l.astype(jnp.int32) + offs[None, :, None]
            local = fused - shard_id * tables.rows_per_shard
            hit = (local >= 0) & (local < tables.rows_per_shard) & m_l
            rows = self._gather_masked(table_shard, local, hit)
            return jax.lax.psum(rows, AXIS_MODEL)

        return shard_map(
            fn,
            mesh=mesh,
            in_specs=(
                P(AXIS_MODEL, None),
                P(batch_axes, None, None),
                P(batch_axes, None, None),
            ),
            out_specs=P(batch_axes, None, None, None),
            check_vma=False,
        )(params["table"], indices, mask)

    # ----------------------------------------------------------- cache refresh

    def gather_rows(
        self, params: dict, row_ids: jax.Array, mesh: Mesh | None = None
    ) -> jax.Array:
        """Fetch fused-table rows by global id (used to materialize the cache).

        row_ids: [K] (may contain INT_MAX padding -> zero rows).
        """
        tables = self.sharded
        if tables is None:
            raise ValueError("no sharded table to gather from")
        valid = row_ids < tables.total_rows

        if mesh is None:
            safe = jnp.clip(row_ids, 0, tables.total_rows - 1)
            rows = jnp.take(params["table"], safe, axis=0)
            return jnp.where(valid[:, None], rows, 0)

        def fn(table_shard, ids):
            shard_id = jax.lax.axis_index(AXIS_MODEL)
            local = ids - shard_id * tables.rows_per_shard
            hit = (local >= 0) & (local < tables.rows_per_shard) & (
                ids < tables.total_rows
            )
            rows = jnp.take(
                table_shard, jnp.clip(local, 0, tables.rows_per_shard - 1), axis=0
            )
            rows = jnp.where(hit[:, None], rows, 0)
            return jax.lax.psum(rows, AXIS_MODEL)

        return shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(AXIS_MODEL, None), P(None)),
            out_specs=P(None, None),
            check_vma=False,
        )(params["table"], row_ids)


def make_hash_cache_from_table(
    emb: DisaggEmbedding,
    params: dict,
    hot_ids: np.ndarray,
    num_slots: int,
    freqs: np.ndarray | None = None,
    admission_threshold: int = 1,
    mesh: Mesh | None = None,
    max_probes: int = 8,
) -> HashCacheState:
    """Materialize a hotcache HashCacheState holding `hot_ids` (fused ids).

    Rows come from the authoritative sharded table (gather_rows), so cached
    lookups stay bit-identical to uncached ones.  `freqs` seeds the LFU
    counters (defaults to rank order: hottest id gets the largest counter, so
    window conflicts resolve the right way)."""
    from repro.hotcache.table import empty_hash_cache

    hot_ids = np.asarray(hot_ids)[: num_slots]
    if freqs is None:
        freqs = np.arange(len(hot_ids), 0, -1, dtype=np.int32)
    state = empty_hash_cache(num_slots, emb.dim, emb.param_dtype)
    if len(hot_ids) == 0:
        return state
    ids_j = jnp.asarray(hot_ids.astype(np.int32))
    rows = emb.gather_rows(
        params, jnp.clip(ids_j, 0, emb.sharded.total_rows - 1), mesh
    )
    rows = jnp.where((ids_j < emb.sharded.total_rows)[:, None], rows, 0)
    state, _ = hc_insert(
        state, ids_j, rows, jnp.asarray(freqs, jnp.int32),
        admission_threshold, max_probes=max_probes,
    )
    return state


def make_cache_from_table(
    emb: DisaggEmbedding,
    params: dict,
    hot_ids: np.ndarray,
    capacity: int,
    mesh: Mesh | None = None,
) -> HotCacheState:
    """Materialize a HotCacheState holding `hot_ids` (fused row ids)."""
    ids = np.full((capacity,), ROW_ID_PAD, dtype=np.int32)
    k = min(capacity, len(hot_ids))
    ids[:k] = np.sort(np.asarray(hot_ids)[:k]).astype(np.int32)
    ids_j = jnp.asarray(ids)
    rows = emb.gather_rows(params, jnp.clip(ids_j, 0, emb.sharded.total_rows - 1), mesh)
    rows = jnp.where((ids_j < emb.sharded.total_rows)[:, None], rows, 0)
    return HotCacheState(ids=ids_j, rows=rows)
