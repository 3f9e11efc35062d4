"""repro.hotcache — device-resident hot-embedding cache subsystem (§3.1.1).

The temporal-locality pillar of FlexEMR as a real cache data structure
instead of the seed's flat replicated slab:

  table      — HashCacheState: open-addressing (linear probe) hash table in
               HBM; jit-functional insert with LFU admission/eviction.
  kernels    — hash probe + miss mask, then the masked gather and per-bag
               pooling in one Pallas kernel; Pallas scatter swap-in.
  ref        — pure-jnp oracles the kernels are validated against.
  policy     — frequency-aware admission (FreqCacheEmbedding-style).
  miss_path  — HostHashCache mirror + TieredLookupService: only cache
               misses become HostLookupService subrequests.

Wired into core.embedding.DisaggEmbedding (device fast path),
core.adaptive_cache (hash-table sizing), runtime.serving (hit-rate /
bytes-saved metrics) and runtime.simulator (hit-rate-dependent wire bytes).
"""
from repro.hotcache.kernels import probe_gather_pool, scatter_update
from repro.hotcache.miss_path import (
    HostHashCache,
    TieredLookupService,
    TieredStats,
)
from repro.hotcache.policy import AdmissionPolicy, select_admissions
from repro.hotcache.table import (
    EMPTY_KEY,
    HashCacheState,
    cache_insert,
    cache_lookup,
    cache_partition_spec,
    decay_freq,
    empty_hash_cache,
    hash_slots,
    hash_slots_np,
    next_pow2,
    probe_slots,
)

__all__ = [
    "AdmissionPolicy",
    "EMPTY_KEY",
    "HashCacheState",
    "HostHashCache",
    "TieredLookupService",
    "TieredStats",
    "cache_insert",
    "cache_lookup",
    "cache_partition_spec",
    "decay_freq",
    "empty_hash_cache",
    "hash_slots",
    "hash_slots_np",
    "next_pow2",
    "probe_gather_pool",
    "probe_slots",
    "scatter_update",
    "select_admissions",
]
