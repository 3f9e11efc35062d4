"""Device-resident open-addressing hash table for hot embedding rows.

This is the data structure behind the FlexEMR §3.1.1 hot cache, replacing the
seed's flat replicated ``(sorted ids, rows)`` slab.  Layout (all HBM, all
jit-compatible pytree leaves):

  keys  [C]    int32   fused row id per slot; EMPTY_KEY marks a vacant slot.
  rows  [C/p, p*D]     the cached embedding rows, packed ``p`` to a 128-lane
                       line (kernels.embedding_bag.pack_rows): slot s lives
                       in line s // p, lanes (s % p) * D onward.  Rows of
                       width D < 128 stored as a plain [C, D] array would be
                       laid out with C on the lanes on a TPU, and every row
                       DMA would relayout the whole cache first.
  freq  [C]    int32   decayed LFU counters (admission/eviction evidence).

``C`` (``num_slots``) is a power of two so the multiplicative hash reduces
with a mask instead of a modulo.  Collisions resolve by **linear probing**
over a bounded window of ``max_probes`` slots — bounded so that both the
Pallas kernel (repro.hotcache.kernels) and the vectorized jnp probe below
have a static trip count, and so a probe never degenerates into a scan.

Invariant: an id, if present, lives at exactly one slot inside its probe
window; inserts that cannot place an id inside the window (all slots taken by
strictly hotter rows) drop it — the cache is *lossy by design*, misses fall
through to the tiered miss path (repro.hotcache.miss_path).

Frequency counters are written only by the insert/maintenance path; lookups
are pure reads so serving steps stay side-effect-free under jit.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.embedding_bag import pack_rows, rows_per_line, take_rows

# Vacant-slot marker. Equals core.embedding.ROW_ID_PAD (int32 max) so padded
# lookup ids can never alias a live key; kept literal here to avoid an import
# cycle (core.embedding imports this module for its cache fast path).
EMPTY_KEY = np.iinfo(np.int32).max

# Knuth multiplicative constant 2654435761 as a wrapped int32.
_HASH_MULT = np.int32(np.uint32(2654435761).astype(np.int32))

DEFAULT_MAX_PROBES = 8


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (and >= 1)."""
    return 1 << max(0, int(n - 1).bit_length()) if n > 1 else 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HashCacheState:
    """Open-addressing hot-row cache (device resident, replicated)."""

    keys: jax.Array  # [C] int32, EMPTY_KEY where vacant
    rows: jax.Array  # [C/p, p*D] line-packed rows (module docstring)
    freq: jax.Array  # [C] int32 LFU counters
    dim: int = dataclasses.field(metadata=dict(static=True))  # row width D

    @property
    def num_slots(self) -> int:
        return int(self.keys.shape[0])

    def occupancy(self) -> jax.Array:
        """Number of live entries (traced scalar)."""
        return (self.keys != EMPTY_KEY).sum()


def empty_hash_cache(
    num_slots: int, dim: int, dtype=jnp.float32
) -> HashCacheState:
    if num_slots & (num_slots - 1):
        raise ValueError(f"num_slots must be a power of two, got {num_slots}")
    return HashCacheState(
        keys=jnp.full((num_slots,), EMPTY_KEY, jnp.int32),
        rows=pack_rows(jnp.zeros((num_slots, dim), dtype)),
        freq=jnp.zeros((num_slots,), jnp.int32),
        dim=dim,
    )


def hash_slots(ids: jax.Array, num_slots: int) -> jax.Array:
    """Home slot of each id: upper bits of the multiplicative hash.

    Works identically under jnp tracing (lookup paths, Pallas index_maps) and
    on concrete int32 arrays; int32 overflow wraps on both sides.
    """
    shift = jnp.int32(max(1, 32 - int(num_slots).bit_length() + 1))
    h = ids.astype(jnp.int32) * _HASH_MULT
    return jax.lax.shift_right_logical(h, shift) & jnp.int32(num_slots - 1)


def hash_slots_np(ids: np.ndarray, num_slots: int) -> np.ndarray:
    """Numpy twin of hash_slots (bit-identical for the non-negative fused row
    ids this repo produces) — used by the host-side cache mirror."""
    shift = max(1, 32 - int(num_slots).bit_length() + 1)
    h = (np.asarray(ids, np.int64) * 2654435761) & 0xFFFFFFFF
    return ((h >> shift) & (num_slots - 1)).astype(np.int64)


def probe_slots(
    ids: jax.Array, num_slots: int, max_probes: int
) -> jax.Array:
    """[..., P] linear-probe window (wrapping) for each id."""
    home = hash_slots(ids, num_slots)
    offs = jnp.arange(max_probes, dtype=jnp.int32)
    return (home[..., None] + offs) & jnp.int32(num_slots - 1)


def probe(
    keys: jax.Array, ids: jax.Array, max_probes: int
) -> tuple[jax.Array, jax.Array]:
    """Vectorized probe: ids [...] -> (slot [...], hit [...]).

    slot is the id's slot where hit, else its home slot.  One 1-D gather
    per probe step: XLA's TPU compiler takes tens of seconds over a single
    [..., P] gather at serving batch sizes."""
    C = keys.shape[0]
    home = hash_slots(ids, C)
    valid = ids != EMPTY_KEY
    slot, hit = home, jnp.zeros(ids.shape, bool)
    for p in reversed(range(max_probes)):  # the first matching step wins
        s = (home + p) & jnp.int32(C - 1)
        match = (jnp.take(keys, s) == ids) & valid
        slot = jnp.where(match, s, slot)
        hit = hit | match
    return slot, hit


def cache_lookup(
    state: HashCacheState,
    ids: jax.Array,
    max_probes: int = DEFAULT_MAX_PROBES,
) -> tuple[jax.Array, jax.Array]:
    """Vectorized probe: ids [...] -> (rows [..., D], hit [...]).

    Pure read (freq untouched) so it is safe inside jit/shard_map serving
    steps.  Misses return zero rows.  This is the portable path; the Pallas
    kernel (kernels.probe_gather_pool) implements the same semantics with
    pooling folded in for the TPU hot loop.
    """
    slot, hit = probe(state.keys, ids, max_probes)
    rows = take_rows(state.rows, slot, state.dim)
    rows = jnp.where(hit[..., None], rows, jnp.zeros((), rows.dtype))
    return rows, hit


@functools.partial(
    jax.jit, static_argnames=("max_probes",)
)
def cache_insert(
    state: HashCacheState,
    ids: jax.Array,  # [K] int32 fused row ids (EMPTY_KEY entries are skipped)
    rows: jax.Array,  # [K, D]
    freqs: jax.Array,  # [K] int32 observed frequency of each id
    admission_threshold: jax.Array | int = 1,
    max_probes: int = DEFAULT_MAX_PROBES,
) -> tuple[HashCacheState, jax.Array]:
    """Functional batch insert with LFU admission/eviction.

    Per id, within its probe window (first rule that applies wins):
      1. key already present        -> refresh the row, freq += freq_i
      2. vacant slot and
         freq_i >= admission_threshold -> claim it (FreqCacheEmbedding-style
         admission: a row must prove itself hot before it earns HBM)
      3. all occupied: evict the window's min-freq victim iff freq_i exceeds
         its counter (strictly — ties keep the incumbent, avoiding thrash)
      4. otherwise the id is dropped (it stays served by the miss path)

    Returns (new_state, admitted [K] bool).  Sequential by construction
    (inserts see earlier inserts) via fori_loop — swap-in batches are small
    (O(cache capacity), off the serving hot path).  The host mirror,
    ``miss_path.HostHashCache.insert``, gives the same table without a loop
    over ids: it commits, in vectorised rounds, every id that no earlier
    pending id's probe window can reach.
    """
    thr = jnp.asarray(admission_threshold, jnp.int32)
    K = ids.shape[0]
    D = state.dim
    pack = rows_per_line(D)
    ids = ids.astype(jnp.int32)
    freqs = freqs.astype(jnp.int32)

    def body(i, carry):
        keys, vals, freq, admitted = carry
        id_i = ids[i]
        f_i = freqs[i]
        window = probe_slots(id_i, state.num_slots, max_probes)  # [P]
        kw = keys[window]
        match = kw == id_i
        vacant = kw == EMPTY_KEY
        has_match = match.any()
        has_vacant = vacant.any()
        match_slot = window[jnp.argmax(match)]
        vacant_slot = window[jnp.argmax(vacant)]
        victim_pos = jnp.argmin(freq[window])
        victim_slot = window[victim_pos]
        victim_freq = freq[victim_slot]

        target = jnp.where(
            has_match, match_slot, jnp.where(has_vacant, vacant_slot, victim_slot)
        )
        fresh_ok = (f_i >= thr) & (has_vacant | (f_i > victim_freq))
        write = (id_i != EMPTY_KEY) & (has_match | fresh_ok)

        keys = keys.at[target].set(jnp.where(write, id_i, keys[target]))
        at = (target // pack, (target % pack) * D)
        old = jax.lax.dynamic_slice(vals, at, (1, D))
        new = jnp.where(write, rows[i].astype(vals.dtype)[None], old)
        vals = jax.lax.dynamic_update_slice(vals, new, at)
        new_f = jnp.where(has_match, freq[target] + f_i, f_i)
        freq = freq.at[target].set(jnp.where(write, new_f, freq[target]))
        admitted = admitted.at[i].set(write)
        return keys, vals, freq, admitted

    keys, vals, freq, admitted = jax.lax.fori_loop(
        0,
        K,
        body,
        (state.keys, state.rows, state.freq, jnp.zeros((K,), bool)),
    )
    return HashCacheState(keys=keys, rows=vals, freq=freq, dim=D), admitted


def decay_freq(state: HashCacheState, factor: float) -> HashCacheState:
    """EMA-style decay of the LFU counters (periodic maintenance)."""
    freq = jnp.floor(state.freq.astype(jnp.float32) * factor).astype(jnp.int32)
    return dataclasses.replace(state, freq=freq)


def cache_partition_spec(state: HashCacheState):
    """Replicated-on-every-chip PartitionSpec pytree for shard_map in_specs."""
    from jax.sharding import PartitionSpec as P

    return HashCacheState(
        keys=P(None), rows=P(None, None), freq=P(None), dim=state.dim
    )
