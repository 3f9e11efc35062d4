"""Decoder-only LM family (dense GQA + MoE variants) with TP/SP/EP sharding.

Layout decisions (per DESIGN.md):
  * weights: TP over `model` (columns of wq/wg/wu, rows of wo/wd), optional
    FSDP over `data` on the other dim; experts sharded over `model` (EP).
  * residual stream: `P(batch, None, None)` (pure TP) or
    `P(batch, model, None)` (Megatron-style sequence parallelism) — config.
  * vocab table + LM head: row-sharded over `model`; token lookup goes through
    the disaggregated psum-combine path (layers.sharded_vocab_embed).
  * decode: KV cache sequence-sharded; flash-decoding (partial-softmax psum)
    combine — the attention instantiation of hierarchical pooling.
  * training: two-level scan with jax.checkpoint around layer groups
    (sqrt-remat), Adafactor for the 100B+ configs.

Heads are padded up to a multiple of the TP degree when needed (arctic's 56
heads -> 64 on a 16-way axis); padded heads have zero wo rows so they are
mathematically inert.  KV heads are sharded when divisible by TP, else
replicated (standard GQA practice).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.sharding import AXIS_DATA, AXIS_MODEL, AXIS_POD
from repro.models import layers as L
from repro.models.moe import MoEConfig, moe_apply_local, moe_init
from repro.utils import round_up


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 128
    qkv_bias: bool = False
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    moe: MoEConfig | None = None
    moe_dense_residual: bool = False  # arctic: dense FFN in parallel with MoE
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    q_block: int = 512
    seq_shard: bool = False  # sequence-parallel residual stream
    remat_groups: int = 0  # 0 -> auto (~sqrt(L))
    fsdp: bool = True  # shard weight rows over `data` too
    microbatches: int = 1  # gradient-accumulation splits of the per-step batch
    # Differentiate through a bf16 copy of the weights (cast once per step):
    # FSDP all-gathers and weight-grad reduce-scatters move bf16 (2x fewer
    # bytes) while the fp32 master lives only in the optimizer.
    bf16_grads: bool = False

    # ---- mesh-dependent geometry -------------------------------------
    def tp(self, mesh: Mesh | None) -> int:
        return mesh.shape[AXIS_MODEL] if mesh is not None else 1

    def padded_heads(self, mesh) -> int:
        return round_up(self.n_heads, self.tp(mesh))

    def kv_sharded(self, mesh) -> bool:
        return self.n_kv_heads % self.tp(mesh) == 0

    def padded_vocab(self, mesh) -> int:
        return round_up(self.vocab, 128 * self.tp(mesh))

    def groups(self) -> int:
        if self.remat_groups:
            return self.remat_groups
        g = max(1, int(math.sqrt(self.n_layers)))
        while self.n_layers % g:
            g -= 1
        return g

    def batch_axes(self, multi_pod: bool) -> tuple[str, ...]:
        return (AXIS_POD, AXIS_DATA) if multi_pod else (AXIS_DATA,)

    def num_params(self, mesh=None) -> int:
        D, F, Vp = self.d_model, self.d_ff, self.padded_vocab(mesh)
        Hd = self.padded_heads(mesh) * self.d_head
        Kd = self.n_kv_heads * self.d_head
        per_layer = D * Hd + 2 * D * Kd + Hd * D + 2 * D
        if self.moe is None or self.moe_dense_residual:
            per_layer += 3 * D * F
        if self.moe is not None:
            per_layer += D * self.moe.num_experts + 3 * self.moe.num_experts * D * self.moe.d_ff
        return self.n_layers * per_layer + 2 * Vp * D + D


# ------------------------------------------------------------------ params


def init_params(cfg: TransformerConfig, key: jax.Array, mesh: Mesh | None = None) -> dict:
    D, dh = cfg.d_model, cfg.d_head
    Hp = cfg.padded_heads(mesh)
    Hkv = cfg.n_kv_heads
    Vp = cfg.padded_vocab(mesh)
    dt = cfg.param_dtype
    ks = jax.random.split(key, 16)

    def nrm(k, shape, fan_in):
        return (jax.random.normal(k, shape, dt) / math.sqrt(fan_in))

    lyr = {
        "ln1": jnp.ones((cfg.n_layers, D), dt),
        "ln2": jnp.ones((cfg.n_layers, D), dt),
        "wq": nrm(ks[0], (cfg.n_layers, D, Hp * dh), D),
        "wk": nrm(ks[1], (cfg.n_layers, D, Hkv * dh), D),
        "wv": nrm(ks[2], (cfg.n_layers, D, Hkv * dh), D),
        "wo": nrm(ks[3], (cfg.n_layers, Hp * dh, D), Hp * dh),
    }
    if cfg.qkv_bias:
        lyr["bq"] = jnp.zeros((cfg.n_layers, Hp * dh), dt)
        lyr["bk"] = jnp.zeros((cfg.n_layers, Hkv * dh), dt)
        lyr["bv"] = jnp.zeros((cfg.n_layers, Hkv * dh), dt)
    if cfg.moe is None or cfg.moe_dense_residual:
        lyr["wg"] = nrm(ks[4], (cfg.n_layers, D, cfg.d_ff), D)
        lyr["wu"] = nrm(ks[5], (cfg.n_layers, D, cfg.d_ff), D)
        lyr["wd"] = nrm(ks[6], (cfg.n_layers, cfg.d_ff, D), cfg.d_ff)
    if cfg.moe is not None:
        E, F = cfg.moe.num_experts, cfg.moe.d_ff
        lyr["router"] = nrm(ks[7], (cfg.n_layers, D, E), D)
        lyr["xg"] = nrm(ks[8], (cfg.n_layers, E, D, F), D)
        lyr["xu"] = nrm(ks[9], (cfg.n_layers, E, D, F), D)
        lyr["xd"] = nrm(ks[10], (cfg.n_layers, E, F, D), F)
    return {
        "embed": nrm(ks[11], (Vp, D), 1.0) * 0.02,
        "layers": lyr,
        "final_ln": jnp.ones((D,), dt),
        "head": nrm(ks[12], (Vp, D), D),
    }


def abstract_params(cfg: TransformerConfig, mesh: Mesh | None = None) -> dict:
    shapes = jax.eval_shape(lambda k: init_params(cfg, k, mesh), jax.random.key(0))
    return shapes


def param_specs(
    cfg: TransformerConfig,
    mesh: Mesh | None,
    training: bool = True,
    fsdp_axes: tuple[str, ...] = (AXIS_DATA,),
) -> dict:
    """PartitionSpecs for every parameter."""
    fsdp = fsdp_axes if (cfg.fsdp and training) else None
    kv_col = AXIS_MODEL if cfg.kv_sharded(mesh) else None
    lyr = {
        "ln1": P(None, None),
        "ln2": P(None, None),
        "wq": P(None, fsdp, AXIS_MODEL),
        "wk": P(None, fsdp, kv_col),
        "wv": P(None, fsdp, kv_col),
        "wo": P(None, AXIS_MODEL, fsdp),
    }
    if cfg.qkv_bias:
        lyr["bq"] = P(None, AXIS_MODEL)
        lyr["bk"] = P(None, kv_col)
        lyr["bv"] = P(None, kv_col)
    if cfg.moe is None or cfg.moe_dense_residual:
        lyr["wg"] = P(None, fsdp, AXIS_MODEL)
        lyr["wu"] = P(None, fsdp, AXIS_MODEL)
        lyr["wd"] = P(None, AXIS_MODEL, fsdp)
    if cfg.moe is not None:
        lyr["router"] = P(None, None, None)
        lyr["xg"] = P(None, AXIS_MODEL, fsdp, None)
        lyr["xu"] = P(None, AXIS_MODEL, fsdp, None)
        lyr["xd"] = P(None, AXIS_MODEL, None, fsdp)
    return {
        "embed": P(AXIS_MODEL, None),
        "layers": lyr,
        "final_ln": P(None),
        "head": P(AXIS_MODEL, None),
    }


# ------------------------------------------------------------------ forward


def _hidden_spec(cfg, batch_axes):
    return P(batch_axes, AXIS_MODEL if cfg.seq_shard else None, None)


def _layer_forward(cfg: TransformerConfig, mesh, batch_axes, x, lp, positions):
    """One transformer block (training / prefill). x: [B,S,D]."""
    dt = cfg.compute_dtype
    B, S, D = x.shape
    Hp = cfg.padded_heads(mesh)
    Hkv, dh = cfg.n_kv_heads, cfg.d_head
    hspec = _hidden_spec(cfg, batch_axes)
    head_spec = P(batch_axes, None, AXIS_MODEL, None)
    kv_spec = P(batch_axes, None, AXIS_MODEL if cfg.kv_sharded(mesh) else None, None)

    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = h @ lp["wq"].astype(dt)
    k = h @ lp["wk"].astype(dt)
    v = h @ lp["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + lp["bq"].astype(dt)
        k = k + lp["bk"].astype(dt)
        v = v + lp["bv"].astype(dt)
    q = L.constrain(q.reshape(B, S, Hp, dh), head_spec)
    k = L.constrain(k.reshape(B, S, Hkv, dh), kv_spec)
    v = L.constrain(v.reshape(B, S, Hkv, dh), kv_spec)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    g = Hp // Hkv
    if g > 1:
        # MHA-ize: repeat KV to the padded head count so attention internals
        # shard cleanly over the 16-way model axis even when Hkv < tp (each
        # chip materializes only its own q-heads' KV slice — no worse than
        # replicated GQA KV, and probs/scores stop being mesh-replicated).
        k_att = L.constrain(jnp.repeat(k, g, axis=2), head_spec)
        v_att = L.constrain(jnp.repeat(v, g, axis=2), head_spec)
    else:
        k_att, v_att = k, v
    attn = L.gqa_prefill_attention(q, k_att, v_att, causal=True, q_block=cfg.q_block)
    attn = L.constrain(attn, head_spec)
    x = x + L.constrain(attn.reshape(B, S, Hp * dh) @ lp["wo"].astype(dt), hspec)

    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    ffn_out = jnp.zeros_like(x)
    if cfg.moe is None or cfg.moe_dense_residual:
        g = jax.nn.silu(h @ lp["wg"].astype(dt)) * (h @ lp["wu"].astype(dt))
        g = L.constrain(g, P(batch_axes, None, AXIS_MODEL))
        ffn_out = ffn_out + L.constrain(g @ lp["wd"].astype(dt), hspec)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        moe_out, aux = _moe_forward(cfg, mesh, batch_axes, h, lp)
        ffn_out = ffn_out + moe_out
    x = x + ffn_out
    return L.constrain(x, hspec), (k, v, aux)


def _moe_forward(cfg, mesh, batch_axes, h, lp):
    """Expert layer: local dispatch per data shard, psum combine over model
    (hierarchical-pooling pattern — see models/moe.py docstring)."""
    B, S, D = h.shape
    moe = cfg.moe

    if mesh is None:
        flat = h.reshape(B * S, D)
        params = {"router": lp["router"], "w_gate": lp["xg"], "w_up": lp["xu"], "w_down": lp["xd"]}
        out, aux = moe_apply_local(params, flat, moe, 1, None)
        return out.reshape(B, S, D), aux

    n_shards = mesh.shape[AXIS_MODEL]

    def fn(h_l, router, xg, xu, xd):
        Bl, Sl, _ = h_l.shape
        flat = h_l.reshape(Bl * Sl, D)
        params = {"router": router, "w_gate": xg, "w_up": xu, "w_down": xd}
        partial, aux = moe_apply_local(
            params, flat, moe, n_shards, jax.lax.axis_index(AXIS_MODEL)
        )
        out = jax.lax.psum(partial, AXIS_MODEL)
        # per-device Switch aux averaged over data shards (GShard practice)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return out.reshape(Bl, Sl, D), aux

    out, aux = shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None, None),
            P(None, None),
            P(AXIS_MODEL, None, None),
            P(AXIS_MODEL, None, None),
            P(AXIS_MODEL, None, None),
        ),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False,
    )(h, lp["router"], lp["xg"], lp["xu"], lp["xd"])
    return out, aux


def forward(
    cfg: TransformerConfig,
    params: dict,
    tokens: jax.Array,  # [B, S]
    mesh: Mesh | None = None,
    batch_axes: tuple[str, ...] = (AXIS_DATA,),
    return_cache: bool = False,
):
    """Full-sequence forward. Returns (logits, aux_loss[, (k_cache, v_cache)])."""
    dt = cfg.compute_dtype
    B, S = tokens.shape
    hspec = _hidden_spec(cfg, batch_axes)
    x = L.sharded_vocab_embed(
        params["embed"], tokens, mesh, batch_axes, out_dtype=dt
    )
    x = L.constrain(x, hspec)
    positions = jnp.arange(S)[None, :]

    lyr = params["layers"]
    G = cfg.groups()
    per = cfg.n_layers // G
    grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((G, per) + a.shape[1:]), lyr
    )

    # Two-level remat (sqrt(L) schedule): the outer scan checkpoints group
    # inputs only; each layer is checkpointed again inside, so a group's
    # backward holds ONE layer's internals at a time.  ~1.33x recompute for
    # an O(sqrt(L)) x O(1)-layer activation footprint.
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one_layer(carry, lp):
        x, aux = carry
        x, (k, v, aux_l) = _layer_forward(cfg, mesh, batch_axes, x, lp, positions)
        kv = (k, v) if return_cache else None
        return (x, aux + aux_l), kv

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one_group(carry, group_params):
        return jax.lax.scan(one_layer, carry, group_params)

    (x, aux), kvs = jax.lax.scan(one_group, (x, jnp.zeros((), jnp.float32)), grouped)

    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = x @ params["head"].astype(dt).T  # [B, S, Vp]
    logits = L.constrain(logits, P(batch_axes, None, AXIS_MODEL))
    if return_cache:
        k_cache, v_cache = kvs
        # [G, per, B, S, Hkv, dh] -> [L, B, S, Hkv, dh]
        k_cache = k_cache.reshape((cfg.n_layers,) + k_cache.shape[2:])
        v_cache = v_cache.reshape((cfg.n_layers,) + v_cache.shape[2:])
        return logits, aux, (k_cache, v_cache)
    return logits, aux


def lm_loss(cfg, logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Causal-LM cross entropy; labels [B,S] (-1 = masked)."""
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    nll = (lse - picked) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


def make_train_step(
    cfg: TransformerConfig,
    optimizer,
    mesh,
    batch_axes=(AXIS_DATA,),
    grad_specs=None,
):
    def loss_fn(p, tokens, labels):
        logits, aux = forward(cfg, p, tokens, mesh, batch_axes)
        return lm_loss(cfg, logits, labels) + aux

    def constrain_grads(g):
        # Pin gradients to the parameter sharding: without this GSPMD is free
        # to all-reduce them data-replicated (params-sized x DP buffers);
        # constraining forces reduce-scatter onto the FSDP shards.
        if grad_specs is None:
            return g
        return jax.tree_util.tree_map(
            lambda x, s: L.constrain(x, s), g, grad_specs,
            is_leaf=lambda x: hasattr(x, "shape"),
        )

    def train_step(params, opt_state, batch):
        M = cfg.microbatches
        if cfg.bf16_grads:
            diff_params = jax.tree_util.tree_map(
                lambda p: p.astype(cfg.compute_dtype) if p.ndim >= 2 else p,
                params,
            )
        else:
            diff_params = params
        if M <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(
                diff_params, batch["tokens"], batch["labels"]
            )
            grads = constrain_grads(grads)
        else:
            # Gradient accumulation: activations scale with B/M; the grad
            # accumulator is the same buffer the update consumes.
            B = batch["tokens"].shape[0]
            toks = batch["tokens"].reshape(M, B // M, -1)
            labs = batch["labels"].reshape(M, B // M, -1)

            def micro(carry, tl):
                loss_acc, grads_acc = carry
                l, g = jax.value_and_grad(loss_fn)(diff_params, *tl)
                g = constrain_grads(g)
                grads_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), grads_acc, g
                )
                return (loss_acc + l, constrain_grads(grads_acc)), None

            zeros = constrain_grads(
                jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
            )
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.zeros(()), zeros), (toks, labs)
            )
            loss = loss / M
            grads = jax.tree_util.tree_map(lambda g: g / M, grads)
        new_params, new_state = optimizer.update(grads, opt_state, params)
        return new_params, new_state, {"loss": loss}

    return train_step


# ------------------------------------------------------------------- decode


def init_decode_cache(cfg: TransformerConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.d_head)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def cache_specs(cfg, batch_axes, seq_axes):
    b = batch_axes if batch_axes else None
    s = seq_axes if seq_axes else None
    return P(None, b, s, None, None)


def decode_step(
    cfg: TransformerConfig,
    params: dict,
    cache: tuple[jax.Array, jax.Array],
    tokens: jax.Array,  # [B]
    pos: jax.Array,  # [] int32 — current write position (cache_len = pos+1... pos)
    mesh: Mesh | None = None,
    batch_axes: tuple[str, ...] = (AXIS_DATA,),
    seq_axes: tuple[str, ...] = (AXIS_MODEL,),
):
    """One autoregressive step against a sequence-sharded KV cache.

    Attention uses the flash-decoding partial-softmax psum combine over
    `seq_axes` (see layers.flash_decode_shard).
    """
    dt = cfg.compute_dtype
    B = tokens.shape[0]
    D, dh = cfg.d_model, cfg.d_head
    Hp = cfg.padded_heads(mesh)
    Hkv = cfg.n_kv_heads
    k_cache, v_cache = cache
    S_max = k_cache.shape[2]

    x = L.sharded_vocab_embed(
        params["embed"], tokens[:, None], mesh, batch_axes, out_dtype=dt
    )  # [B,1,D]
    posb = pos[None, None] if pos.ndim == 0 else pos[:, None]

    if mesh is not None:
        seq_sizes = [mesh.shape[a] for a in seq_axes]
        n_seq_shards = int(np.prod(seq_sizes)) if seq_sizes else 1
    else:
        n_seq_shards = 1
    S_loc = S_max // n_seq_shards

    def attn_shardmap(q, k_l, v_l, k_new, v_new, pos_):
        # q: [B_l, Hp, dh]; k_l/v_l: [B_l, S_loc, Hkv, dh] (this seq shard)
        if seq_axes:
            idx = jnp.zeros((), jnp.int32)
            for a in seq_axes:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        else:
            idx = jnp.zeros((), jnp.int32)
        start = idx * S_loc
        k_l = L.kv_cache_update_shard(k_l, k_new, pos_, start)
        v_l = L.kv_cache_update_shard(v_l, v_new, pos_, start)
        out = L.flash_decode_shard(
            q, k_l, v_l, pos_ + 1, start, combine_axes=tuple(seq_axes)
        )
        return out, k_l, v_l

    lyr = params["layers"]

    def body(carry, scanned):
        # Whole cache rides in the carry and is updated in place per layer
        # (dynamic_update_slice on the carry lets XLA keep one aliased buffer
        # instead of xs/ys double-buffering a multi-GB cache).
        x, k_cache, v_cache, li = carry
        lp = scanned
        k_c = k_cache[li]
        v_c = v_cache[li]
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = (h @ lp["wq"].astype(dt)).reshape(B, Hp, dh)
        k_new = (h @ lp["wk"].astype(dt)).reshape(B, Hkv, dh)
        v_new = (h @ lp["wv"].astype(dt)).reshape(B, Hkv, dh)
        if cfg.qkv_bias:
            q = q + lp["bq"].astype(dt).reshape(Hp, dh)
            k_new = k_new + lp["bk"].astype(dt).reshape(Hkv, dh)
            v_new = v_new + lp["bv"].astype(dt).reshape(Hkv, dh)
        q = L.apply_rope(q[:, None], posb, cfg.rope_theta)[:, 0]
        k_new = L.apply_rope(k_new[:, None], posb, cfg.rope_theta)[:, 0]

        if mesh is None:
            k_c = L.kv_cache_update_shard(k_c, k_new, pos, jnp.zeros((), jnp.int32))
            v_c = L.kv_cache_update_shard(v_c, v_new, pos, jnp.zeros((), jnp.int32))
            attn = L.flash_decode_shard(
                q, k_c, v_c, pos + 1, jnp.zeros((), jnp.int32), combine_axes=()
            )
        else:
            b = batch_axes if batch_axes else None
            kv_spec = P(b, seq_axes if seq_axes else None, None, None)
            attn, k_c, v_c = shard_map(
                attn_shardmap,
                mesh=mesh,
                in_specs=(
                    P(b, None, None),
                    kv_spec,
                    kv_spec,
                    P(b, None, None),
                    P(b, None, None),
                    P(),
                ),
                out_specs=(P(b, None, None), kv_spec, kv_spec),
                check_vma=False,
            )(q, k_c, v_c, k_new, v_new, pos)

        x = x + (attn.reshape(B, 1, Hp * dh) @ lp["wo"].astype(dt))
        h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        ffn = jnp.zeros_like(x)
        if cfg.moe is None or cfg.moe_dense_residual:
            g = jax.nn.silu(h2 @ lp["wg"].astype(dt)) * (h2 @ lp["wu"].astype(dt))
            ffn = ffn + g @ lp["wd"].astype(dt)
        if cfg.moe is not None:
            moe_out, _ = _moe_forward(cfg, mesh, batch_axes, h2, lp)
            ffn = ffn + moe_out
        k_cache = jax.lax.dynamic_update_index_in_dim(k_cache, k_c, li, 0)
        v_cache = jax.lax.dynamic_update_index_in_dim(v_cache, v_c, li, 0)
        return (x + ffn, k_cache, v_cache, li + 1), None

    (x, k_cache, v_cache, _), _ = jax.lax.scan(
        body, (x, k_cache, v_cache, jnp.zeros((), jnp.int32)), lyr
    )
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = (x[:, 0] @ params["head"].astype(dt).T)
    logits = L.constrain(logits, P(batch_axes if batch_axes else None, AXIS_MODEL))
    return logits, (k_cache, v_cache)


def prefill(
    cfg: TransformerConfig,
    params: dict,
    tokens: jax.Array,
    mesh: Mesh | None = None,
    batch_axes: tuple[str, ...] = (AXIS_DATA,),
):
    """Prefill: full forward returning last-position logits + KV caches
    (caches come back [L,B,S,Hkv,dh], ready for sequence-sharded decode)."""
    logits, aux, (k_cache, v_cache) = forward(
        cfg, params, tokens, mesh, batch_axes, return_cache=True
    )
    return logits[:, -1], (k_cache, v_cache)
