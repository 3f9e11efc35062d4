"""Shared dense layers: norms, MLPs, rotary, GQA attention (prefill+decode).

Sharding philosophy: parameters carry explicit PartitionSpecs (returned by the
model's `param_specs`); activations are pinned at layer boundaries with
`with_sharding_constraint`.  Attention decode uses an explicit shard_map
(flash-decoding combine over sequence-sharded KV) because GSPMD cannot derive
that schedule on its own.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.sharding import AXIS_DATA, AXIS_MODEL, AXIS_POD

# --------------------------------------------------------------------- utils


def constrain(x: jax.Array, spec: P | None) -> jax.Array:
    """with_sharding_constraint that no-ops when tracing without a mesh."""
    if spec is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # no mesh in context (single-device smoke tests)


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return jax.random.uniform(key, (d_in, d_out), dtype, -scale, scale)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)


def mlp_params(key, sizes: Sequence[int], dtype=jnp.float32, bias: bool = True) -> dict:
    """Plain MLP stack parameters: sizes = [d_in, h1, ..., d_out]."""
    params = {}
    for i in range(len(sizes) - 1):
        key, k = jax.random.split(key)
        params[f"w{i}"] = dense_init(k, sizes[i], sizes[i + 1], dtype)
        if bias:
            params[f"b{i}"] = jnp.zeros((sizes[i + 1],), dtype)
    return params


def mlp_apply(
    params: dict,
    x: jax.Array,
    act: Callable = jax.nn.relu,
    final_act: bool = False,
) -> jax.Array:
    n = len([k for k in params if k.startswith("w")])
    for i in range(n):
        x = x @ params[f"w{i}"].astype(x.dtype)
        if f"b{i}" in params:
            x = x + params[f"b{i}"].astype(x.dtype)
        if i < n - 1 or final_act:
            x = act(x)
    return x


# --------------------------------------------------------------------- rotary


def rope_frequencies(d_head: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float64) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., T, H, dh]; positions: broadcastable to [..., T]."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(dh, theta), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, dh/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention


def gqa_prefill_attention(
    q: jax.Array,  # [B, S, H, dh]
    k: jax.Array,  # [B, S, Hkv, dh]
    v: jax.Array,  # [B, S, Hkv, dh]
    causal: bool = True,
    q_block: int = 1024,
) -> jax.Array:
    """Query-chunked exact attention: memory O(q_block * S) instead of O(S^2).

    The dense counterpart of the Pallas flash kernel (kernels/flash_attention);
    used on the XLA path (and by the dry-run, where Pallas cannot lower to the
    CPU placeholder backend).
    """
    B, S, H, dh = q.shape
    Hkv = k.shape[2]
    groups = H // Hkv
    scale = 1.0 / math.sqrt(dh)
    qr = q.reshape(B, S, Hkv, groups, dh)

    q_block = min(q_block, S)
    n_blocks = (S + q_block - 1) // q_block
    pad = n_blocks * q_block - S
    if pad:
        qr = jnp.pad(qr, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qr = qr.reshape(B, n_blocks, q_block, Hkv, groups, dh)
    kpos = jnp.arange(S)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def block(carry, inputs):
        # remat per q-block: the [q_block, S] score/prob tiles are recomputed
        # in backward, never stored — flash-attention memory behaviour on the
        # XLA path (the Pallas kernel does the same in VMEM on real TPUs).
        qb, blk_idx = inputs  # [B, q_block, Hkv, groups, dh]
        qpos = blk_idx * q_block + jnp.arange(q_block)
        scores = jnp.einsum(
            "bqhgd,bshd->bhgqs", qb, k, preferred_element_type=jnp.float32
        ) * scale
        row_ok = (qpos < S)[:, None]
        if causal:
            valid = row_ok & (qpos[:, None] >= kpos[None, :])
        else:
            valid = jnp.broadcast_to(row_ok, (qpos.shape[0], S))
        scores = jnp.where(valid[None, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bhgqs,bshd->bqhgd", probs.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return carry, out.astype(q.dtype)

    _, outs = jax.lax.scan(
        block, None, (jnp.moveaxis(qr, 1, 0), jnp.arange(n_blocks))
    )
    out = jnp.moveaxis(outs, 0, 1).reshape(B, n_blocks * q_block, Hkv, groups, dh)
    if pad:
        out = out[:, :S]
    return out.reshape(B, S, H, dh)


def flash_decode_shard(
    q: jax.Array,  # [B, H, dh] — full heads (replicated across model axis)
    k_local: jax.Array,  # [B, S_loc, Hkv, dh] — sequence shard
    v_local: jax.Array,
    cache_len: jax.Array,  # [] or [B] — valid prefix length
    shard_start: jax.Array,  # [] — global position of this shard's row 0
    combine_axes: tuple[str, ...],
) -> jax.Array:
    """Per-shard flash-decoding: partial softmax over the local KV chunk,
    combined across sequence shards with (max, sum, out) psum algebra.

    This is the TPU analogue of FlexEMR's hierarchical pooling applied to
    attention: each shard reduces what it owns; only [B,H,dh]-sized partials
    cross the network.
    """
    B, S_loc, Hkv, dh = k_local.shape
    H = q.shape[1]
    groups = H // Hkv
    scale = 1.0 / math.sqrt(dh)
    qr = q.reshape(B, Hkv, groups, dh)

    pos = shard_start + jnp.arange(S_loc)
    if cache_len.ndim == 0:
        valid = pos[None, :] < cache_len  # [1, S_loc]
    else:
        valid = pos[None, :] < cache_len[:, None]  # [B, S_loc]

    scores = jnp.einsum(
        "bhgd,bshd->bhgs", qr, k_local, preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    local_max = scores.max(axis=-1)  # [B,Hkv,groups]
    safe_max = jnp.where(jnp.isfinite(local_max), local_max, 0.0)
    probs = jnp.exp(scores - safe_max[..., None])
    probs = jnp.where(valid[:, None, None, :], probs, 0.0)
    l_local = probs.sum(axis=-1)  # [B,Hkv,groups]
    o_local = jnp.einsum(
        "bhgs,bshd->bhgd", probs.astype(v_local.dtype), v_local,
        preferred_element_type=jnp.float32,
    )

    g_max = local_max
    for ax in combine_axes:
        g_max = jax.lax.pmax(g_max, ax)
    scale_f = jnp.where(
        jnp.isfinite(local_max), jnp.exp(local_max - g_max), 0.0
    )
    l_scaled = l_local * scale_f
    o_scaled = o_local * scale_f[..., None]
    l_g = jax.lax.psum(l_scaled, combine_axes)
    o_g = jax.lax.psum(o_scaled, combine_axes)
    out = o_g / jnp.maximum(l_g[..., None], 1e-30)
    return out.reshape(B, H, dh).astype(q.dtype)


def kv_cache_update_shard(
    cache: jax.Array,  # [B, S_loc, Hkv, dh] — this shard's slice
    new_kv: jax.Array,  # [B, Hkv, dh]
    pos: jax.Array,  # [] global write position
    shard_start: jax.Array,
) -> jax.Array:
    """Write one token into a sequence-sharded KV cache (owner shard only)."""
    S_loc = cache.shape[1]
    local = pos - shard_start
    in_range = (local >= 0) & (local < S_loc)
    idx = jnp.clip(local, 0, S_loc - 1)
    current = jax.lax.dynamic_slice_in_dim(cache, idx, 1, axis=1)
    value = jnp.where(in_range, new_kv[:, None], current)
    return jax.lax.dynamic_update_slice_in_dim(cache, value.astype(cache.dtype), idx, axis=1)


# --------------------------------------------------- sharded vocab embedding


def sharded_vocab_embed(
    table: jax.Array,  # [V_padded, D] — row-sharded over `model`
    tokens: jax.Array,  # [B, S]
    mesh: Mesh | None,
    batch_axes: tuple[str, ...] = (AXIS_DATA,),
    out_dtype=jnp.bfloat16,
) -> jax.Array:
    """Token embedding through the disaggregated-lookup path (psum of partial
    gathers) — the LM instantiation of the paper's hierarchical combine
    (nnz=1 degenerate pooling)."""
    V, D = table.shape

    if mesh is None:
        return jnp.take(table, tokens, axis=0).astype(out_dtype)

    n_shards = mesh.shape[AXIS_MODEL]
    rows = V // n_shards

    def fn(tbl, tok):
        m = jax.lax.axis_index(AXIS_MODEL)
        local = tok - m * rows
        hit = (local >= 0) & (local < rows)
        emb = jnp.take(tbl, jnp.clip(local, 0, rows - 1), axis=0)
        emb = jnp.where(hit[..., None], emb.astype(out_dtype), 0)
        return jax.lax.psum(emb, AXIS_MODEL)

    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(AXIS_MODEL, None), P(batch_axes, None)),
        out_specs=P(batch_axes, None, None),
        check_vma=False,
    )(table, tokens)
