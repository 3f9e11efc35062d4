"""Pallas TPU kernels for the perf-critical compute layers.

  embedding_bag    — fused gather+pool via per-row DMA from a line-packed
                     table (the paper's embedding-lookup hot path,
                     VMEM-fused pooling).
  dot_interaction  — DLRM pairwise-dot gram matrix on the MXU.
  flash_attention  — causal GQA online-softmax attention (LM prefill path).
  flash_decode     — split-K decode against a long KV cache, scalar-prefetch
                     cache length (LM decode path).

Each <name>.py holds the pl.pallas_call + BlockSpecs, ops.py the jit'd
wrappers, ref.py the pure-jnp oracles the tests sweep against.

The hot-embedding-cache kernels (hash probe + gather + pool + miss mask,
and the scatter swap-in) live with their data structure in
repro.hotcache.kernels.
"""
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ops import (
    bag_lookup,
    dot_interaction_triu,
    embedding_bag,
    flash_attention,
)

__all__ = [
    "bag_lookup",
    "dot_interaction_triu",
    "embedding_bag",
    "flash_attention",
    "flash_decode",
]
