"""Core disaggregated-embedding invariants: routing, pooling paths, cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.embedding import DisaggEmbedding, make_cache_from_table
from repro.core.sharding import (
    FusedTables,
    RangeRouter,
    TableSpec,
    make_fused_tables,
    rebalance_ranges,
)


def _specs():
    return [
        TableSpec("a", 997, nnz=4, pooling="sum"),
        TableSpec("b", 512, nnz=2, pooling="mean"),
        TableSpec("c", 33, nnz=1, pooling="sum"),
    ]


def _batch(rng, specs, B=8):
    F = len(specs)
    nnz = max(s.nnz for s in specs)
    idx = np.zeros((B, F, nnz), np.int32)
    msk = np.zeros((B, F, nnz), bool)
    for f, s in enumerate(specs):
        idx[:, f, : s.nnz] = rng.integers(0, s.vocab, (B, s.nnz))
        fill = rng.integers(1, s.nnz + 1, B)
        msk[:, f, : s.nnz] = np.arange(s.nnz)[None] < fill[:, None]
    return idx, msk


# ------------------------------------------------------------------ routing


@given(num_shards=st.sampled_from([1, 2, 4, 8, 16]), seed=st.integers(0, 100))
@settings(max_examples=25, deadline=None)
def test_router_range_invariants(num_shards, seed):
    tables = make_fused_tables(_specs(), dim=8, num_shards=num_shards)
    router = RangeRouter(tables)
    rng = np.random.default_rng(seed)
    f = rng.integers(0, 3, 64)
    vocabs = np.array([s.vocab for s in _specs()])
    i = (rng.random(64) * vocabs[f]).astype(np.int64)
    rows = router.global_rows(f, i)
    shards = router.shard_of(rows)
    # every row lands in exactly the shard whose range contains it
    for (lo, hi), s in router.routing_table():
        inside = (rows >= lo) & (rows < hi)
        assert np.all(shards[inside] == s)
    assert np.all(shards >= 0) and np.all(shards < num_shards)
    # ranges tile [0, total_rows) exactly
    table = router.routing_table()
    assert table[0][0][0] == 0
    assert table[-1][0][1] == tables.total_rows
    for (r1, _), (r2, _) in zip(table, table[1:]):
        assert r1[1] == r2[0]


def test_router_rejects_out_of_vocab():
    tables = make_fused_tables(_specs(), dim=8, num_shards=4)
    router = RangeRouter(tables)
    with pytest.raises(IndexError):
        router.global_rows([0], [997])


@given(seed=st.integers(0, 50))
@settings(max_examples=20, deadline=None)
def test_rebalance_exhaustive_and_monotonic(seed):
    tables = make_fused_tables(_specs(), dim=8, num_shards=8)
    rng = np.random.default_rng(seed)
    load = rng.random(8) * 10 + 0.1
    b = rebalance_ranges(load, tables)
    assert b[0] == 0 and b[-1] == tables.total_rows
    assert np.all(np.diff(b) >= 0)


# ------------------------------------------------------- lookup equivalences


def test_lookup_paths_match_reference(trivial_mesh, rng):
    specs = _specs()
    idx, msk = _batch(rng, specs)
    for mode in ("baseline", "hierarchical"):
        for rep in ((), (2,)):
            emb = DisaggEmbedding(
                specs=specs, dim=16, num_shards=1, mode=mode,
                replicated_fields=rep,
            )
            params = emb.init(jax.random.key(0))
            ref = emb.lookup_reference(params, jnp.asarray(idx), jnp.asarray(msk))
            out = jax.jit(
                lambda p, i, m, e=emb: e.lookup(p, i, m, mesh=trivial_mesh)
            )(params, jnp.asarray(idx), jnp.asarray(msk))
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6
            )


def test_chunked_lookup_matches(trivial_mesh, rng):
    specs = _specs()
    idx, msk = _batch(rng, specs)
    emb = DisaggEmbedding(specs=specs, dim=16, num_shards=1)
    params = emb.init(jax.random.key(1))
    ref = emb.lookup_reference(params, jnp.asarray(idx), jnp.asarray(msk))
    for chunks in (2, 3):
        out = jax.jit(
            lambda p, i, m: emb.lookup(p, i, m, mesh=trivial_mesh, num_chunks=chunks)
        )(params, jnp.asarray(idx), jnp.asarray(msk))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6
        )


@given(cache_size=st.sampled_from([16, 64, 256]), seed=st.integers(0, 30))
@settings(max_examples=10, deadline=None)
def test_hot_cache_is_transparent(cache_size, seed):
    """Property: any hot set leaves lookup results unchanged."""
    import jax as _jax
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    specs = _specs()
    rng = np.random.default_rng(seed)
    idx, msk = _batch(rng, specs)
    emb = DisaggEmbedding(specs=specs, dim=16, num_shards=1)
    params = emb.init(_jax.random.key(2))
    total = emb.sharded.raw_rows
    hot = rng.choice(total, min(cache_size, total), replace=False)
    cache = make_cache_from_table(emb, params, hot, cache_size, mesh=mesh)
    ref = emb.lookup_reference(params, jnp.asarray(idx), jnp.asarray(msk))
    out = _jax.jit(
        lambda p, i, m, c: emb.lookup(p, i, m, mesh=mesh, cache=c)
    )(params, jnp.asarray(idx), jnp.asarray(msk), cache)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)


def test_lookup_rows_unpooled(trivial_mesh, rng):
    specs = _specs()
    idx, msk = _batch(rng, specs)
    emb = DisaggEmbedding(specs=specs, dim=16, num_shards=1)
    params = emb.init(jax.random.key(3))
    rows = jax.jit(
        lambda p, i, m: emb.lookup_rows(p, i, m, mesh=trivial_mesh)
    )(params, jnp.asarray(idx), jnp.asarray(msk))
    assert rows.shape == idx.shape + (16,)
    # pooled(sum fields) consistency
    ref = emb.lookup_reference(params, jnp.asarray(idx), jnp.asarray(msk))
    summed = np.asarray(rows).sum(axis=2)
    np.testing.assert_allclose(summed[:, 0], np.asarray(ref)[:, 0], rtol=1e-5, atol=1e-6)


def test_gradients_flow_to_table(rng):
    specs = _specs()
    idx, msk = _batch(rng, specs)
    emb = DisaggEmbedding(specs=specs, dim=8, num_shards=1)
    params = emb.init(jax.random.key(4))
    g = jax.grad(
        lambda p: emb.lookup_reference(p, jnp.asarray(idx), jnp.asarray(msk)).sum()
    )(params)
    touched = np.unique(
        np.asarray(idx[msk])  # not fused, but nonzero grads must exist
    )
    assert float(np.abs(np.asarray(g["table"])).sum()) > 0
