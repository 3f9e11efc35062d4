"""Recsys model family on top of the disaggregated embedding core.

Five architectures (the paper's own workload class):
  dlrm       — the paper's Fig-1 reference model (RMC2-shaped): bottom MLP on
               dense features, embedding bags, pairwise dot interaction, top MLP.
  wide_deep  — Wide&Deep: linear ("wide") table + deep MLP over embeddings.
  autoint    — self-attention feature interaction over field embeddings.
  mind       — multi-interest capsule routing over user behaviour sequences.
  two_tower  — dual-encoder retrieval with in-batch sampled softmax.

All sparse lookups go through core.DisaggEmbedding, so every model supports
`mode=baseline|hierarchical`, hot-row caching, field replication, chunked
lookups and comm compression uniformly.  The batch is sharded over the data
axes for the lookup; dense compute is resharded over (data x model) so the
"ranker" side uses the whole mesh (helper `dense_shard`).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.embedding import DisaggEmbedding, HotCacheState
from repro.core.sharding import AXIS_DATA, AXIS_MODEL, AXIS_POD, TableSpec
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    arch: str  # dlrm | wide_deep | autoint | mind | two_tower | dcn | deepfm
    tables: tuple[TableSpec, ...]
    embed_dim: int
    n_dense: int = 0
    mlp: tuple[int, ...] = (1024, 512, 256)
    bottom_mlp: tuple[int, ...] = (512, 256, 64)
    # autoint
    attn_layers: int = 3
    attn_heads: int = 2
    d_attn: int = 32
    # mind
    n_interests: int = 4
    capsule_iters: int = 3
    hist_len: int = 50
    # two-tower: how many leading tables belong to the user tower
    user_tables: int = 2
    # dcn-v2
    n_cross: int = 3
    cross_rank: int = 64
    # lookup strategy (the paper's knobs)
    mode: str = "hierarchical"
    num_chunks: int = 1
    replicated_fields: tuple[int, ...] = ()
    comm_dtype: Any = None
    use_wide: bool = False
    # fold the wide table into extra columns of the main fused table: one
    # lookup (one index all-gather + one reduce-scatter) serves both halves
    fuse_wide: bool = False
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.arch == "dlrm" and self.bottom_mlp[-1] != self.embed_dim:
            raise ValueError(
                "dlrm: bottom_mlp must end at embed_dim so the dense vector "
                "joins the dot interaction"
            )

    @property
    def num_fields(self) -> int:
        return len(self.tables)

    def num_shards_for(self, mesh) -> int:
        if mesh is None:
            return 1
        if self.mode == "mesh2d":
            import math

            return math.prod(mesh.shape.values())
        return mesh.shape[AXIS_MODEL]

    @property
    def max_nnz(self) -> int:
        return max(s.nnz for s in self.tables)

    def embedding(self, num_shards: int) -> DisaggEmbedding:
        dim = self.embed_dim + (8 if (self.use_wide and self.fuse_wide) else 0)
        return DisaggEmbedding(
            specs=self.tables,
            dim=dim,
            num_shards=num_shards,
            mode=self.mode,
            replicated_fields=self.replicated_fields,
            comm_dtype=self.comm_dtype,
            param_dtype=self.param_dtype,
        )

    def wide_embedding(self, num_shards: int) -> DisaggEmbedding:
        return DisaggEmbedding(
            specs=self.tables,
            dim=8,  # 8-wide rows keep the fused layout lane-aligned; col 0 used
            num_shards=num_shards,
            mode=self.mode,
            param_dtype=self.param_dtype,
        )

    def num_embedding_rows(self) -> int:
        return sum(t.vocab for t in self.tables)


def dense_shard(x: jax.Array, batch_axes: tuple[str, ...]) -> jax.Array:
    """Reshard batch over (data x model) for the dense-NN stage."""
    axes = tuple(batch_axes) + (AXIS_MODEL,)
    return L.constrain(x, P(axes, *([None] * (x.ndim - 1))))


# ------------------------------------------------------------------- params


def init_params(cfg: RecsysConfig, key: jax.Array, num_shards: int = 1) -> dict:
    dt = cfg.param_dtype
    emb = cfg.embedding(num_shards)
    k_emb, k_wide, k1, k2, k3, k4 = jax.random.split(key, 6)
    params: dict = {"emb": emb.init(k_emb)}
    F, D = cfg.num_fields, cfg.embed_dim

    if cfg.arch == "dlrm":
        n_vecs = F + 1  # field embeddings + bottom-MLP vector
        n_pairs = n_vecs * (n_vecs + 1) // 2  # upper triangle incl. diagonal
        params["bottom"] = L.mlp_params(k1, (cfg.n_dense,) + cfg.bottom_mlp, dt)
        top_in = n_pairs + cfg.bottom_mlp[-1]
        params["top"] = L.mlp_params(k2, (top_in,) + cfg.mlp + (1,), dt)
    elif cfg.arch == "wide_deep":
        if cfg.use_wide and not cfg.fuse_wide:
            params["wide"] = cfg.wide_embedding(num_shards).init(k_wide)
        deep_in = F * D + cfg.n_dense
        params["deep"] = L.mlp_params(k1, (deep_in,) + cfg.mlp + (1,), dt)
        if cfg.n_dense:
            params["dense_lin"] = L.dense_init(k3, cfg.n_dense, 1, dt)
    elif cfg.arch == "autoint":
        d_a, H = cfg.d_attn, cfg.attn_heads
        lyrs = []
        d_in = D
        for i in range(cfg.attn_layers):
            k1, ka, kb, kc, kd = jax.random.split(k1, 5)
            lyrs.append(
                {
                    "wq": L.dense_init(ka, d_in, d_a, dt),
                    "wk": L.dense_init(kb, d_in, d_a, dt),
                    "wv": L.dense_init(kc, d_in, d_a, dt),
                    "wres": L.dense_init(kd, d_in, d_a, dt),
                }
            )
            d_in = d_a
        params["attn"] = lyrs
        params["out"] = L.dense_init(k2, F * d_in, 1, dt)
    elif cfg.arch == "mind":
        params["bilinear"] = L.dense_init(k1, D, D, dt)
        params["out_mlp"] = L.mlp_params(k2, (D, D), dt)
    elif cfg.arch == "two_tower":
        Fu = cfg.user_tables
        params["user_mlp"] = L.mlp_params(k1, (Fu * D,) + cfg.mlp, dt)
        params["item_mlp"] = L.mlp_params(
            k2, ((F - Fu) * D,) + cfg.mlp, dt
        )
        params["temp"] = jnp.asarray(0.05, dt)
    elif cfg.arch == "dcn":
        # DCN-v2, low-rank cross: x_{l+1} = x0 * (U_l (V_l^T x_l) + b_l) + x_l
        d0 = F * D + cfg.n_dense
        cross = []
        for _ in range(cfg.n_cross):
            k1, ku, kv = jax.random.split(k1, 3)
            cross.append(
                {
                    "u": L.dense_init(ku, cfg.cross_rank, d0, dt),
                    "v": L.dense_init(kv, d0, cfg.cross_rank, dt),
                    "b": jnp.zeros((d0,), dt),
                }
            )
        params["cross"] = cross
        params["deep"] = L.mlp_params(k2, (d0,) + cfg.mlp, dt)
        params["out"] = L.dense_init(k3, d0 + cfg.mlp[-1], 1, dt)
    elif cfg.arch == "deepfm":
        # FM first-order term = a dim-8 wide table (col 0), shared embeddings
        params["wide"] = cfg.wide_embedding(num_shards).init(k_wide)
        params["deep"] = L.mlp_params(
            k1, (F * D + cfg.n_dense,) + cfg.mlp + (1,), dt
        )
    else:
        raise ValueError(cfg.arch)
    return params


def abstract_params(cfg: RecsysConfig, num_shards: int = 1) -> dict:
    return jax.eval_shape(
        lambda k: init_params(cfg, k, num_shards), jax.random.key(0)
    )


def param_specs(
    cfg: RecsysConfig, num_shards: int, batch_axes: tuple[str, ...] = (AXIS_DATA,)
) -> dict:
    """Embedding tables row-sharded on `model` (paper layout) or the whole
    mesh (`mesh2d`); dense params replicated."""
    shapes = abstract_params(cfg, num_shards)
    table_spec = (
        P(tuple(batch_axes) + (AXIS_MODEL,), None)
        if cfg.mode == "mesh2d"
        else P(AXIS_MODEL, None)
    )

    def spec(path, leaf):
        name = jax.tree_util.keystr(path)
        if "emb" in name or "wide" in name:
            if "rep_table" in name:
                return P(None, None)
            return table_spec
        return P(*([None] * len(leaf.shape)))

    return jax.tree_util.tree_map_with_path(spec, shapes)


# ------------------------------------------------------------------ forward


def _lookup(cfg, emb, params, batch, mesh, batch_axes, cache):
    return emb.lookup(
        params["emb"],
        batch["indices"],
        batch["mask"],
        mesh=mesh,
        cache=cache,
        batch_axes=batch_axes,
        num_chunks=cfg.num_chunks,
    )


def dot_interaction(vecs: jax.Array) -> jax.Array:
    """DLRM pairwise dots: [B, F, D] -> [B, F*(F+1)/2] (upper triangle w/o diag
    plus self-dots row — we keep i<=j upper incl. diag, FB's variant)."""
    B, F, D = vecs.shape
    prods = jnp.einsum("bfd,bgd->bfg", vecs, vecs, preferred_element_type=jnp.float32)
    iu, ju = np.triu_indices(F)
    return prods[:, iu, ju]


def forward(
    cfg: RecsysConfig,
    params: dict,
    batch: dict,
    mesh: Mesh | None = None,
    batch_axes: tuple[str, ...] = (AXIS_DATA,),
    cache: HotCacheState | None = None,
) -> jax.Array:
    """Returns per-sample logits/scores.

    batch keys: indices [B,F,nnz] int32, mask [B,F,nnz] bool,
    dense [B,n_dense] (if any), hist/hist_mask (mind), target (mind).
    """
    dt = cfg.compute_dtype
    num_shards = cfg.num_shards_for(mesh)
    emb = cfg.embedding(num_shards)

    if cfg.arch == "mind":
        return _mind_forward(cfg, emb, params, batch, mesh, batch_axes)

    pooled = _lookup(cfg, emb, params, batch, mesh, batch_axes, cache)  # [B,F,D]
    pooled = dense_shard(pooled.astype(dt), batch_axes)
    B = pooled.shape[0]

    if cfg.arch == "dlrm":
        dense = dense_shard(batch["dense"].astype(dt), batch_axes)
        bot = L.mlp_apply(params["bottom"], dense, final_act=True)  # [B, D]
        inter = dot_interaction(
            jnp.concatenate([bot[:, None, :], pooled], axis=1)
        ).astype(dt)
        top_in = jnp.concatenate([inter, bot], axis=-1)
        return L.mlp_apply(params["top"], top_in)[:, 0]

    if cfg.arch == "wide_deep":
        D = cfg.embed_dim
        wide_cols = pooled[:, :, D:] if (cfg.use_wide and cfg.fuse_wide) else None
        pooled = pooled[:, :, :D] if wide_cols is not None else pooled
        feats = [pooled.reshape(B, -1)]
        logit = jnp.zeros((B,), dt)
        if cfg.n_dense:
            dense = dense_shard(batch["dense"].astype(dt), batch_axes)
            feats.append(dense)
            logit = logit + (dense @ params["dense_lin"].astype(dt))[:, 0]
        deep = L.mlp_apply(params["deep"], jnp.concatenate(feats, -1))[:, 0]
        if wide_cols is not None:
            logit = logit + wide_cols[..., 0].sum(axis=1).astype(dt)
        elif cfg.use_wide:
            wide_emb = cfg.wide_embedding(num_shards)
            wide = wide_emb.lookup(
                params["wide"], batch["indices"], batch["mask"],
                mesh=mesh, batch_axes=batch_axes, num_chunks=cfg.num_chunks,
            )
            wide = dense_shard(wide, batch_axes)
            logit = logit + wide[..., 0].sum(axis=1).astype(dt)
        return deep + logit

    if cfg.arch == "autoint":
        x = pooled  # [B, F, D]
        H = cfg.attn_heads
        for lp in params["attn"]:
            q = (x @ lp["wq"].astype(dt)).reshape(B, -1, H, cfg.d_attn // H)
            k = (x @ lp["wk"].astype(dt)).reshape(B, -1, H, cfg.d_attn // H)
            v = (x @ lp["wv"].astype(dt)).reshape(B, -1, H, cfg.d_attn // H)
            scores = jnp.einsum("bfhd,bghd->bhfg", q, k,
                                preferred_element_type=jnp.float32)
            probs = jax.nn.softmax(scores / math.sqrt(q.shape[-1]), axis=-1)
            o = jnp.einsum("bhfg,bghd->bfhd", probs.astype(dt), v)
            o = o.reshape(B, x.shape[1], cfg.d_attn)
            x = jax.nn.relu(o + x @ lp["wres"].astype(dt))
        return (x.reshape(B, -1) @ params["out"].astype(dt))[:, 0]

    if cfg.arch == "two_tower":
        u, v = two_tower_encode(cfg, params, pooled)
        return jnp.sum(u * v, axis=-1) / params["temp"].astype(dt)

    if cfg.arch == "dcn":
        feats = [pooled.reshape(B, -1)]
        if cfg.n_dense:
            feats.append(dense_shard(batch["dense"].astype(dt), batch_axes))
        x0 = jnp.concatenate(feats, -1)
        x = x0
        for lp in params["cross"]:
            low = x @ lp["v"].astype(dt)  # [B, r]
            x = x0 * (low @ lp["u"].astype(dt) + lp["b"].astype(dt)) + x
        deep = L.mlp_apply(params["deep"], x0, final_act=True)
        return (jnp.concatenate([x, deep], -1) @ params["out"].astype(dt))[:, 0]

    if cfg.arch == "deepfm":
        # FM 2nd order: 0.5 * ((sum_f v_f)^2 - sum_f v_f^2), summed over dim
        s = pooled.sum(axis=1)
        fm2 = 0.5 * (s * s - (pooled * pooled).sum(axis=1)).sum(axis=-1)
        wide_emb = cfg.wide_embedding(num_shards)
        wide = wide_emb.lookup(
            params["wide"], batch["indices"], batch["mask"],
            mesh=mesh, batch_axes=batch_axes,
        )
        fm1 = dense_shard(wide, batch_axes)[..., 0].sum(axis=1).astype(dt)
        feats = [pooled.reshape(B, -1)]
        if cfg.n_dense:
            feats.append(dense_shard(batch["dense"].astype(dt), batch_axes))
        deep = L.mlp_apply(params["deep"], jnp.concatenate(feats, -1))[:, 0]
        return fm1 + fm2.astype(dt) + deep

    raise ValueError(cfg.arch)


def two_tower_encode(cfg, params, pooled):
    """pooled [B, F, D] -> (user [B, d], item [B, d]), both L2-normalized."""
    B = pooled.shape[0]
    Fu = cfg.user_tables
    u = L.mlp_apply(params["user_mlp"], pooled[:, :Fu].reshape(B, -1))
    v = L.mlp_apply(params["item_mlp"], pooled[:, Fu:].reshape(B, -1))
    u = u / jnp.linalg.norm(u, axis=-1, keepdims=True).clip(1e-6)
    v = v / jnp.linalg.norm(v, axis=-1, keepdims=True).clip(1e-6)
    return u, v


def _squash(x: jax.Array) -> jax.Array:
    n2 = jnp.sum(x * x, axis=-1, keepdims=True)
    return (n2 / (1.0 + n2)) * x / jnp.sqrt(n2 + 1e-9)


def _mind_forward(cfg, emb, params, batch, mesh, batch_axes):
    """MIND: behaviour-sequence capsule routing -> K interests -> label-aware
    attention against the target item."""
    dt = cfg.compute_dtype
    # hist: [B, Hist] item ids (field 0 of tables); target: [B]
    hist, hist_mask, target = batch["hist"], batch["hist_mask"], batch["target"]
    B, Hh = hist.shape
    rows = emb.lookup_rows(
        params["emb"], hist[:, None, :], hist_mask[:, None, :],
        mesh=mesh, batch_axes=batch_axes,
    )[:, 0]  # [B, Hist, D]
    tgt = emb.lookup_rows(
        params["emb"], target[:, None, None],
        jnp.ones((B, 1, 1), bool), mesh=mesh, batch_axes=batch_axes,
    )[:, 0, 0]  # [B, D]
    rows = dense_shard(rows.astype(dt), batch_axes)
    tgt = dense_shard(tgt.astype(dt), batch_axes)
    hist_mask = dense_shard(hist_mask, batch_axes)

    eW = rows @ params["bilinear"].astype(dt)  # [B, Hist, D]
    K = cfg.n_interests
    b = jnp.zeros((rows.shape[0], Hh, K), jnp.float32)

    def routing_iter(b, _):
        w = jax.nn.softmax(b, axis=-1) * hist_mask[..., None]
        z = jnp.einsum("bhk,bhd->bkd", w.astype(dt), eW)
        c = _squash(z)  # [B, K, D]
        b_new = b + jnp.einsum("bhd,bkd->bhk", eW, c).astype(jnp.float32)
        return b_new, c

    b, cs = jax.lax.scan(routing_iter, b, None, length=cfg.capsule_iters)
    interests = cs[-1]  # [B, K, D]
    interests = L.mlp_apply(params["out_mlp"], interests, act=jax.nn.relu)

    att = jax.nn.softmax(
        (jnp.einsum("bkd,bd->bk", interests, tgt) * 2.0).astype(jnp.float32), axis=-1
    )
    user = jnp.einsum("bk,bkd->bd", att.astype(dt), interests)
    return jnp.sum(user * tgt, axis=-1)


# -------------------------------------------------------------------- loss


def bce_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def in_batch_softmax_loss(cfg, params, pooled, log_q=None):
    """Two-tower training loss: in-batch sampled softmax with logQ correction."""
    u, v = two_tower_encode(cfg, params, pooled)
    logits = (u @ v.T).astype(jnp.float32) / params["temp"].astype(jnp.float32)
    if log_q is not None:
        logits = logits - log_q[None, :]
    labels = jnp.arange(logits.shape[0])
    return jnp.mean(
        jax.nn.logsumexp(logits, axis=-1)
        - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    )


def make_train_step(cfg: RecsysConfig, optimizer, mesh,
                    batch_axes=(AXIS_DATA,)):
    num_shards = cfg.num_shards_for(mesh)

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            if cfg.arch == "two_tower":
                emb = cfg.embedding(num_shards)
                pooled = emb.lookup(
                    p["emb"], batch["indices"], batch["mask"], mesh=mesh,
                    batch_axes=batch_axes, num_chunks=cfg.num_chunks,
                )
                pooled = dense_shard(pooled.astype(cfg.compute_dtype), batch_axes)
                return in_batch_softmax_loss(cfg, p, pooled, batch.get("log_q"))
            logits = forward(cfg, p, batch, mesh, batch_axes)
            if cfg.arch == "mind":
                # BPR-style: positive target vs shuffled negatives
                pos = logits
                neg = jnp.roll(logits, 1)
                return -jnp.mean(jax.nn.log_sigmoid(pos - neg))
            return bce_loss(logits, batch["labels"])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_state = optimizer.update(grads, opt_state, params)
        return new_params, new_state, {"loss": loss}

    return train_step


# --------------------------------------------------------------- retrieval


def mind_user_interests(cfg, params, batch, mesh, batch_axes):
    """hist [B,H] -> interest capsules [B, K, D] (shared with _mind_forward)."""
    dt = cfg.compute_dtype
    num_shards = cfg.num_shards_for(mesh)
    emb = cfg.embedding(num_shards)
    hist, hist_mask = batch["hist"], batch["hist_mask"]
    rows = emb.lookup_rows(
        params["emb"], hist[:, None, :], hist_mask[:, None, :],
        mesh=mesh, batch_axes=batch_axes,
    )[:, 0].astype(dt)
    eW = rows @ params["bilinear"].astype(dt)
    K = cfg.n_interests
    b = jnp.zeros((rows.shape[0], hist.shape[1], K), jnp.float32)

    def routing_iter(b, _):
        w = jax.nn.softmax(b, axis=-1) * hist_mask[..., None]
        z = jnp.einsum("bhk,bhd->bkd", w.astype(dt), eW)
        c = _squash(z)
        return b + jnp.einsum("bhd,bkd->bhk", eW, c).astype(jnp.float32), c

    _, cs = jax.lax.scan(routing_iter, b, None, length=cfg.capsule_iters)
    return L.mlp_apply(params["out_mlp"], cs[-1], act=jax.nn.relu)


def mind_retrieval(
    cfg: RecsysConfig,
    params: dict,
    batch: dict,  # hist [1,H], hist_mask, cand_ids [N]
    k: int = 100,
    mesh: Mesh | None = None,
    batch_axes: tuple[str, ...] = (AXIS_DATA,),
):
    """Score one user's interests against N candidate items; top-k.

    Candidates are batch-sharded over the data axes; each shard scores its
    slice (score = max over interests of <e_cand, interest>) and contributes
    a local top-k — partial reduce where the data lives, as in §3.1.2.
    """
    interests = mind_user_interests(cfg, params, batch, mesh, ())  # [1,K,D]
    num_shards = cfg.num_shards_for(mesh)
    emb = cfg.embedding(num_shards)
    cand = batch["cand_ids"]  # [N]
    N = cand.shape[0]
    rows = emb.lookup_rows(
        params["emb"], cand[:, None, None], jnp.ones((N, 1, 1), bool),
        mesh=mesh, batch_axes=batch_axes,
    )[:, 0, 0].astype(cfg.compute_dtype)  # [N, D]
    scores = jnp.einsum("nd,bkd->bnk", rows, interests).max(axis=-1)  # [1,N]

    if mesh is None:
        return jax.lax.top_k(scores, k)

    def fn(sc_l):
        idx = jnp.zeros((), jnp.int32)
        for a in batch_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        n_loc = sc_l.shape[1]
        val, pos = jax.lax.top_k(sc_l, min(k, n_loc))
        gpos = pos + idx * n_loc
        vals = jax.lax.all_gather(val, batch_axes, axis=1, tiled=True)
        poss = jax.lax.all_gather(gpos, batch_axes, axis=1, tiled=True)
        gval, gidx = jax.lax.top_k(vals, k)
        return gval, jnp.take_along_axis(poss, gidx, axis=1)

    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(None, batch_axes),),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(scores)


def retrieval_topk(
    cfg: RecsysConfig,
    params: dict,
    batch: dict,
    candidates: jax.Array,  # [N, d] precomputed item-tower embeddings
    k: int = 100,
    mesh: Mesh | None = None,
    batch_axes: tuple[str, ...] = (AXIS_DATA,),
):
    """Score one (or few) user queries against N candidates and return top-k.

    Candidates are sharded over the whole mesh; each shard computes a local
    top-k and only [k]-sized partials are gathered — the retrieval analogue of
    hierarchical pooling (partial reduce where the data lives).
    """
    num_shards = cfg.num_shards_for(mesh)
    emb = cfg.embedding(num_shards)
    pooled = emb.lookup(
        params["emb"], batch["indices"], batch["mask"], mesh=mesh,
        batch_axes=batch_axes,
    )
    B = pooled.shape[0]
    Fu = cfg.user_tables
    u = L.mlp_apply(params["user_mlp"], pooled[:, :Fu].reshape(B, -1))
    u = u / jnp.linalg.norm(u, axis=-1, keepdims=True).clip(1e-6)

    if mesh is None:
        scores = u @ candidates.T
        return jax.lax.top_k(scores, k)

    all_axes = tuple(mesh.axis_names)

    def fn(u_l, cand_l):
        idx = jnp.zeros((), jnp.int32)
        for a in all_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        n_loc = cand_l.shape[0]
        scores = u_l @ cand_l.T  # [B, n_loc]
        val, pos = jax.lax.top_k(scores, min(k, n_loc))
        gpos = pos + idx * n_loc
        # gather the per-shard top-k everywhere, then reduce to global top-k
        vals = jax.lax.all_gather(val, all_axes, axis=1, tiled=True)
        poss = jax.lax.all_gather(gpos, all_axes, axis=1, tiled=True)
        gval, gidx = jax.lax.top_k(vals, k)
        return gval, jnp.take_along_axis(poss, gidx, axis=1)

    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(None, None), P(all_axes, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )(u, candidates)
