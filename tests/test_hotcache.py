"""repro.hotcache: hash table vs dict oracle, Pallas kernels vs ref oracles,
and the tiered miss path end-to-end on zipf-skewed traffic."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.embedding import DisaggEmbedding, make_hash_cache_from_table
from repro.core.lookup_engine import HostLookupService
from repro.core.sharding import TableSpec, make_fused_tables
from repro.data import synthetic as syn
from repro.hotcache import ref as HREF
from repro.hotcache.kernels import probe_gather_pool, scatter_update
from repro.hotcache.miss_path import HostHashCache, TieredLookupService
from repro.hotcache.policy import AdmissionPolicy
from repro.kernels.embedding_bag import pack_rows, unpack_rows
from repro.hotcache.table import (
    EMPTY_KEY,
    cache_insert,
    cache_lookup,
    empty_hash_cache,
    hash_slots,
    hash_slots_np,
    next_pow2,
)


# ------------------------------------------------------------- hash geometry


def test_hash_slots_np_matches_jnp():
    ids = np.concatenate(
        [np.arange(1000), np.array([EMPTY_KEY, 2**31 - 2, 0])]
    ).astype(np.int32)
    for C in (16, 256, 4096):
        got_np = hash_slots_np(ids, C)
        got_j = np.asarray(hash_slots(jnp.asarray(ids), C))
        np.testing.assert_array_equal(got_np, got_j)


def test_next_pow2():
    assert [next_pow2(n) for n in (0, 1, 2, 3, 640, 1024)] == [
        1, 1, 2, 4, 1024, 1024,
    ]


# -------------------------------------------------- insert/probe/evict oracle


def _dict_oracle_insert(table: dict, id_i, row_i, f_i, C, P, thr):
    """Independent python simulation of the table.cache_insert rules.

    `table` maps slot -> [key, row, freq].
    """
    if id_i == EMPTY_KEY:
        return
    window = [(int(hash_slots_np(np.array([id_i]), C)[0]) + p) & (C - 1)
              for p in range(P)]
    for s in window:  # rule 1: refresh
        if s in table and table[s][0] == id_i:
            table[s][1] = row_i
            table[s][2] += f_i
            return
    if f_i < thr:  # admission gate
        return
    for s in window:  # rule 2: claim a vacant slot
        if s not in table:
            table[s] = [id_i, row_i, f_i]
            return
    victim = min(window, key=lambda s: table[s][2])  # rule 3: LFU evict
    if f_i > table[victim][2]:
        table[victim] = [id_i, row_i, f_i]


@given(seed=st.integers(0, 40), thr=st.sampled_from([1, 3, 8]))
@settings(max_examples=12, deadline=None)
def test_insert_probe_evict_matches_dict_oracle(seed, thr):
    rng = np.random.default_rng(seed)
    C, D, P = 64, 8, 4
    n_ops = 150
    ids = rng.integers(0, 500, n_ops).astype(np.int32)  # duplicates included
    rows = rng.normal(size=(n_ops, D)).astype(np.float32)
    freqs = rng.integers(1, 12, n_ops).astype(np.int32)

    state = empty_hash_cache(C, D)
    state, _ = cache_insert(
        state, jnp.asarray(ids), jnp.asarray(rows), jnp.asarray(freqs),
        thr, max_probes=P,
    )

    oracle: dict = {}
    for i in range(n_ops):
        _dict_oracle_insert(oracle, int(ids[i]), rows[i], int(freqs[i]), C, P, thr)

    keys = np.asarray(state.keys)
    freq = np.asarray(state.freq)
    vals = np.asarray(unpack_rows(state.rows, D))
    want_keys = np.full((C,), EMPTY_KEY, np.int64)
    for s, (k, r, f) in oracle.items():
        want_keys[s] = k
        assert freq[s] == f, (s, k)
        np.testing.assert_array_equal(vals[s], r)
    np.testing.assert_array_equal(keys.astype(np.int64), want_keys)

    # the numpy host mirror replays the same sequence to the same table,
    # one id a call and the whole sequence in one call
    host = HostHashCache(C, D, max_probes=P)
    for i in range(n_ops):
        host.insert(ids[i : i + 1], rows[i : i + 1], freqs[i : i + 1], thr)
    np.testing.assert_array_equal(host.keys, want_keys)
    bulk = HostHashCache(C, D, max_probes=P)
    bulk.insert(ids, rows, freqs, thr)
    np.testing.assert_array_equal(bulk.keys, want_keys)

    # every id the table claims to hold is returned exactly on lookup
    probe_rows, hit = cache_lookup(state, jnp.asarray(ids), max_probes=P)
    hit = np.asarray(hit)
    live = {k: r for (k, r, f) in oracle.values()}
    for i in range(n_ops):
        assert hit[i] == (int(ids[i]) in live)
        if hit[i]:
            np.testing.assert_array_equal(np.asarray(probe_rows)[i], live[int(ids[i])])


# ------------------------------------------------------- Pallas kernel vs ref


@pytest.mark.parametrize(
    "C,D,bags,nnz,probes",
    [(64, 128, 4, 1, 4), (256, 128, 16, 4, 8), (512, 256, 8, 8, 8),
     (256, 64, 16, 4, 8), (128, 32, 8, 3, 4)],
)
def test_probe_gather_pool_kernel_vs_ref(C, D, bags, nnz, probes, rng):
    state = empty_hash_cache(C, D)
    n_ins = int(C * 0.6)
    ins_ids = rng.choice(100_000, n_ins, replace=False).astype(np.int32)
    ins_rows = rng.normal(size=(n_ins, D)).astype(np.float32)
    state, _ = cache_insert(
        state, jnp.asarray(ins_ids), jnp.asarray(ins_rows),
        jnp.asarray(rng.integers(1, 9, n_ins).astype(np.int32)),
        1, max_probes=probes,
    )
    # queries: ~60% resident ids, rest cold + some padded-invalid slots
    q = rng.choice(ins_ids, bags * nnz).astype(np.int32)
    cold = rng.random(q.shape) < 0.4
    q[cold] = rng.integers(200_000, 300_000, int(cold.sum())).astype(np.int32)
    q[rng.random(q.shape) < 0.1] = EMPTY_KEY
    w = np.where(rng.random(q.shape) > 0.2, rng.random(q.shape), 0.0).astype(
        np.float32
    )
    pooled, miss = probe_gather_pool(
        state, jnp.asarray(q), jnp.asarray(w), bags,
        max_probes=probes, interpret=True,
    )
    want_pooled, want_miss = HREF.probe_gather_pool_ref(
        state.keys, unpack_rows(state.rows, D), jnp.asarray(q),
        jnp.asarray(w), bags, probes,
    )
    np.testing.assert_allclose(
        np.asarray(pooled), np.asarray(want_pooled), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(miss), np.asarray(want_miss))
    # kernel probe agrees with the jnp cache_lookup fast path too
    _, hit = cache_lookup(state, jnp.asarray(q), max_probes=probes)
    np.testing.assert_array_equal(~np.asarray(hit), np.asarray(miss))


def _check_scatter_update(D, rng):
    C, K = 128, 32
    values = jnp.asarray(rng.normal(size=(C, D)).astype(np.float32))
    slots = rng.choice(C, K, replace=False).astype(np.int32)
    # neighbours sharing a line, and a repeated slot (last write wins)
    slots[1], slots[2] = slots[0] ^ 1, slots[0]
    rows = rng.normal(size=(K, D)).astype(np.float32)
    want = HREF.scatter_update_ref(values, jnp.asarray(slots), jnp.asarray(rows))
    got = scatter_update(
        pack_rows(values), jnp.asarray(slots), jnp.asarray(rows), interpret=True
    )
    np.testing.assert_array_equal(
        np.asarray(unpack_rows(got, D)), np.asarray(want)
    )


def test_scatter_update_kernel_vs_ref(rng):
    _check_scatter_update(128, rng)


def test_scatter_update_kernel_vs_ref_packed_lines(rng):
    _check_scatter_update(64, rng)


# ----------------------------------------------- DisaggEmbedding integration


def test_hash_cache_transparent_in_lookup(trivial_mesh, rng):
    specs = [
        TableSpec("a", 997, nnz=4),
        TableSpec("b", 512, nnz=2, pooling="mean"),
        TableSpec("c", 33, nnz=1),
    ]
    B, F, nnz = 8, 3, 4
    idx = np.zeros((B, F, nnz), np.int32)
    msk = np.zeros((B, F, nnz), bool)
    for f, s in enumerate(specs):
        idx[:, f, : s.nnz] = rng.integers(0, s.vocab, (B, s.nnz))
        msk[:, f, : s.nnz] = True
    emb = DisaggEmbedding(specs=specs, dim=16, num_shards=1)
    params = emb.init(jax.random.key(0))
    ref = emb.lookup_reference(params, jnp.asarray(idx), jnp.asarray(msk))
    hot = rng.choice(emb.sharded.raw_rows, 200, replace=False)
    cache = make_hash_cache_from_table(emb, params, hot, 512, mesh=trivial_mesh)
    out = jax.jit(
        lambda p, i, m, c: emb.lookup(p, i, m, mesh=trivial_mesh, cache=c)
    )(params, jnp.asarray(idx), jnp.asarray(msk), cache)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6
    )


# ------------------------------------------------------- tiered miss path e2e


def test_tiered_miss_path_zipf_bytes_and_correctness(rng):
    specs = (
        TableSpec("a", 40_000, nnz=4),
        TableSpec("b", 10_000, nnz=2, pooling="mean"),
        TableSpec("c", 64, nnz=1),
    )
    dim, shards = 16, 4
    emb = DisaggEmbedding(specs=specs, dim=dim, num_shards=shards)
    params = emb.init(jax.random.key(1))
    tables = make_fused_tables(specs, dim, shards)
    svc = HostLookupService(tables, np.asarray(params["table"]))
    tiered = TieredLookupService(
        svc,
        num_slots=8192,
        policy=AdmissionPolicy(admission_threshold=1.5, max_swap_in=4096),
        refresh_every=2,
    )
    try:
        def batch():
            return syn.recsys_batch(rng, specs, 64, alpha=1.3)

        for _ in range(12):  # warm the cache
            b = batch()
            tiered.lookup(b["indices"], b["mask"])
        tiered.stats = type(tiered.stats)()  # measure steady state only

        for _ in range(20):
            b = batch()
            out = tiered.lookup(b["indices"], b["mask"])
            ref = emb.lookup_reference(
                params, jnp.asarray(b["indices"]), jnp.asarray(b["mask"])
            )
            np.testing.assert_allclose(
                out, np.asarray(ref), rtol=1e-4, atol=1e-5
            )
        s = tiered.stats
        assert s.hit_rate > 0.5, s.summary()
        total_moved = s.bytes_network + s.bytes_swap_in
        assert total_moved * 2 <= s.bytes_no_cache, s.summary()  # >= 2x saving
    finally:
        svc.close()


def _colliding_ids(C, P, n, start=0):
    """n ids whose probe windows all share one home slot (true collisions)."""
    home = hash_slots_np(np.arange(start, start + 200_000), C)
    target = home[0]
    ids = np.flatnonzero(home == target)[:n] + start
    assert len(ids) == n, "not enough colliding ids in range"
    return ids.astype(np.int64)


def test_host_cache_insert_collision_and_full_table(rng):
    """satellite: the window-conflict paths of HostHashCache.insert —
    vacant fill, admission gate, LFU eviction, tie-keeps-incumbent — on ids
    that genuinely share one probe window."""
    C, D, P = 64, 8, 4
    cache = HostHashCache(C, D, max_probes=P)
    ids = _colliding_ids(C, P, P + 3)
    rows = rng.normal(size=(len(ids), D)).astype(np.float32)

    # 1. fill the window with the first P ids (freqs 10..10+P-1)
    n = cache.insert(ids[:P], rows[:P], np.arange(10, 10 + P, dtype=float), 1.0)
    assert n == P
    assert cache.occupancy == P
    for i in range(P):
        r, hit = cache.lookup(ids[i : i + 1])
        assert hit[0]
        np.testing.assert_array_equal(r[0], rows[i])

    # 2. window full: a colder challenger (freq below the window min) drops
    n = cache.insert(ids[P : P + 1], rows[P : P + 1], np.array([5.0]), 1.0)
    assert n == 0 and cache.occupancy == P
    _, hit = cache.lookup(ids[P : P + 1])
    assert not hit[0]

    # 3. tie with the coldest incumbent (freq 10) also keeps the incumbent
    n = cache.insert(ids[P + 1 : P + 2], rows[P + 1 : P + 2], np.array([10.0]), 1.0)
    assert n == 0
    _, hit = cache.lookup(ids[:1])
    assert hit[0]

    # 4. strictly hotter challenger evicts the window's LFU victim (ids[0])
    n = cache.insert(ids[P + 2 : P + 3], rows[P + 2 : P + 3], np.array([99.0]), 1.0)
    assert n == 1 and cache.occupancy == P
    _, hit = cache.lookup(ids[:1])
    assert not hit[0]  # victim gone
    r, hit = cache.lookup(ids[P + 2 : P + 3])
    assert hit[0]
    np.testing.assert_array_equal(r[0], rows[P + 2])

    # 5. admission gate: a fresh id below threshold never claims even a
    # vacant slot elsewhere in the table
    cold_id = np.array([next(
        i for i in range(1, 10_000)
        if i not in set(ids.tolist())
    )], np.int64)
    n = cache.insert(cold_id, rows[:1], np.array([1.0]), admission_threshold=5.0)
    assert n == 0
    # 6. re-inserting a resident id refreshes the row and accumulates freq
    new_row = rng.normal(size=(1, D)).astype(np.float32)
    slot, _ = cache.probe(ids[P + 2 : P + 3])
    f_before = cache.freq[slot[0]]
    n = cache.insert(ids[P + 2 : P + 3], new_row, np.array([2.0]), 1.0)
    assert n == 1
    assert cache.freq[slot[0]] == f_before + 2.0
    r, hit = cache.lookup(ids[P + 2 : P + 3])
    np.testing.assert_array_equal(r[0], new_row[0])
    # 7. EMPTY_KEY entries are skipped outright
    n = cache.insert(
        np.array([EMPTY_KEY], np.int64), rows[:1], np.array([50.0]), 1.0
    )
    assert n == 0 and cache.occupancy == P


def _sequential_insert(
    cache, ids, rows, freqs, admission_threshold=1.0, prefetched=False
):
    """The row-at-a-time HostHashCache.insert: the oracle for the rounds."""
    if cache.num_slots == 0:
        return 0
    admitted = 0
    home = hash_slots_np(ids, cache.num_slots)
    for i in range(len(ids)):
        id_i = int(ids[i])
        if id_i == EMPTY_KEY:
            continue
        window = (home[i] + np.arange(cache.max_probes)) & (cache.num_slots - 1)
        kw = cache.keys[window]
        match = np.flatnonzero(kw == id_i)
        if len(match):
            t = window[match[0]]
            cache.rows[t] = rows[i]
            cache.freq[t] += freqs[i]
            cache.prefetched[t] &= prefetched
            admitted += 1
            continue
        if freqs[i] < admission_threshold:
            continue
        vacant = np.flatnonzero(kw == EMPTY_KEY)
        if len(vacant):
            t = window[vacant[0]]
        else:
            t = window[np.argmin(cache.freq[window])]
            if freqs[i] <= cache.freq[t]:
                continue  # incumbent is at least as hot: keep it
            if cache.prefetched[t]:
                cache.prefetch_evicted += 1  # speculation lost the slot
        cache.keys[t] = id_i
        cache.rows[t] = rows[i]
        cache.freq[t] = freqs[i]
        cache.prefetched[t] = prefetched
        admitted += 1
    return admitted


def _random_calls(rng, thr, prefetched=False, n_calls=4):
    """Calls of random ids with duplicates and EMPTY_KEY entries into a
    64-slot table, so windows fill and evict."""
    calls = []
    for _ in range(n_calls):
        ids = rng.integers(0, 160, 120).astype(np.int64)
        ids[rng.random(120) < 0.08] = EMPTY_KEY
        freqs = rng.integers(1, 12, 120).astype(np.float64)
        calls.append((ids, freqs, thr, prefetched))
    return calls


def _case_random(thr):
    def build(rng):
        return HostHashCache(64, 8, max_probes=4), _random_calls(rng, thr)
    return build


def _case_prefetched(flag):
    def build(rng):
        # the table already holds prefetched rows before the checked calls
        cache = HostHashCache(64, 8, max_probes=4)
        ids = rng.permutation(300)[:48].astype(np.int64)
        cache.insert(ids, rng.normal(size=(48, 8)).astype(np.float32),
                     rng.integers(1, 6, 48).astype(np.float64), 1.0,
                     prefetched=True)
        assert cache.prefetched.sum() > 0
        return cache, _random_calls(rng, 1.0, prefetched=flag)
    return build


def _case_colliding(rng):
    # ids sharing one home slot fill their window; equal freqs make every
    # victim choice a tie and every challenger at the min freq a loser
    C, P = 64, 4
    ids = _colliding_ids(C, P, 12)
    calls = []
    for _ in range(3):
        sel = rng.permutation(np.r_[ids, ids[:4]])
        freqs = rng.choice([2.0, 2.0, 3.0, 5.0], len(sel))
        calls.append((sel, freqs, 1.0, bool(rng.random() < 0.5)))
    return HostHashCache(C, 8, max_probes=P), calls


def _case_refresh(rng):
    # the refresh's shape: unique hot ids, half the table's slots a call,
    # each call overlapping the last, until eviction engages
    n, C = 8192, 16384
    universe = rng.permutation(200_000)[: 4 * n].astype(np.int64)
    calls = []
    for c in range(5):
        ids = rng.permutation(universe[c * n // 3 : c * n // 3 + n])
        calls.append((ids, rng.random(n) * 50.0, 1.0, False))
    return HostHashCache(C, 8), calls


@pytest.mark.parametrize(
    "build",
    [_case_random(1.0), _case_random(3.0), _case_random(8.0),
     _case_prefetched(True), _case_prefetched(False), _case_colliding,
     _case_refresh],
    ids=["random-thr1", "random-thr3", "random-thr8", "prefetched-true",
         "prefetched-false", "colliding-ties", "refresh-shaped"],
)
def test_bulk_insert_matches_sequential_oracle(build):
    """HostHashCache.insert commits in rounds what the row loop does one id
    at a time: the same table, counters and admitted count, bit for bit."""
    rng = np.random.default_rng(15)
    cache, calls = build(rng)
    oracle = copy.deepcopy(cache)
    evicted = 0
    for ids, freqs, thr, prefetched in calls:
        rows = rng.normal(size=(len(ids), cache.rows.shape[1])).astype(
            np.float32)
        rounds = cache.insert_rounds
        before = cache.keys[cache.keys != EMPTY_KEY]
        want = _sequential_insert(oracle, ids, rows, freqs, thr, prefetched)
        assert cache.insert(ids, rows, freqs, thr, prefetched) == want
        assert cache.insert_rounds > rounds
        for name in ("keys", "rows", "freq", "prefetched"):
            np.testing.assert_array_equal(
                getattr(cache, name), getattr(oracle, name), err_msg=name)
        assert cache.prefetch_evicted == oracle.prefetch_evicted
        evicted += len(np.setdiff1d(before, cache.keys))
        cache.decay(0.5)
        oracle.decay(0.5)
    assert evicted > 0  # the LFU eviction path ran


def test_tiered_refresh_insert_decay_stress(rng):
    """satellite: TieredLookupService.refresh under many insert/decay cycles
    on a drifting zipf stream — table invariants must hold throughout."""
    specs = (TableSpec("a", 20_000, nnz=4), TableSpec("b", 4_000, nnz=2))
    emb = DisaggEmbedding(specs=specs, dim=8, num_shards=2)
    params = emb.init(jax.random.key(7))
    tables = make_fused_tables(specs, 8, 2)
    svc = HostLookupService(tables, np.asarray(params["table"]))
    tiered = TieredLookupService(
        svc,
        num_slots=512,  # small: force heavy eviction churn
        policy=AdmissionPolicy(admission_threshold=1.5, max_swap_in=256),
        refresh_every=1,  # refresh (insert+decay) every batch
    )
    table_np = np.asarray(params["table"])
    try:
        for step in range(30):
            # drift: rotate the popular range every 10 steps
            lo = (step // 10) * 5_000
            b = syn.recsys_batch(rng, specs, 32, alpha=1.3)
            b["indices"][:, 0, :] = (b["indices"][:, 0, :] + lo) % 20_000
            tiered.lookup(b["indices"], b["mask"])

            cache = tiered.cache
            live = cache.keys != EMPTY_KEY
            # invariant: live keys are unique
            lk = cache.keys[live]
            assert len(np.unique(lk)) == len(lk)
            assert cache.occupancy <= cache.num_slots
            # invariant: every live key is findable by its own probe...
            if len(lk):
                _, hit = cache.probe(lk)
                assert hit.all()
                # ...and holds the authoritative row bit-for-bit
                r, _ = cache.lookup(lk)
                np.testing.assert_array_equal(r, table_np[lk])
            # invariant: decay keeps frequencies finite and non-negative
            assert (cache.freq >= 0).all() and np.isfinite(cache.freq).all()
        assert tiered.stats.admitted > 0
        assert tiered.stats.hit_rate > 0.1  # the cache did real work
    finally:
        svc.close()


def test_tiered_lookup_handles_all_hot_batch(rng):
    """A batch fully absorbed by the cache must not post any subrequest."""
    specs = (TableSpec("a", 128, nnz=2),)
    emb = DisaggEmbedding(specs=specs, dim=8, num_shards=2)
    params = emb.init(jax.random.key(3))
    tables = make_fused_tables(specs, 8, 2)
    svc = HostLookupService(tables, np.asarray(params["table"]))
    tiered = TieredLookupService(svc, num_slots=256, refresh_every=10**9)
    try:
        # preload the whole vocab
        ids = np.arange(128, dtype=np.int64)
        tiered.cache.insert(
            ids, np.asarray(params["table"])[:128], np.full(128, 10), 1.0
        )
        b = syn.recsys_batch(rng, specs, 16)
        before = tiered.stats.bytes_network
        out = tiered.lookup(b["indices"], b["mask"])
        assert tiered.stats.bytes_network == before
        assert tiered.stats.hit_rate == 1.0
        ref = emb.lookup_reference(
            params, jnp.asarray(b["indices"]), jnp.asarray(b["mask"])
        )
        np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-5, atol=1e-6)
    finally:
        svc.close()
