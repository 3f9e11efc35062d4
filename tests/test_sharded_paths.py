"""Multi-device SPMD equivalence tests.

These need >1 device, so they run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (per the dry-run rule the
main test process keeps the real single device).
"""
import os
import pathlib
import subprocess
import sys

import pytest

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=560,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


PREAMBLE = """
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
"""


def test_embedding_paths_sharded():
    _run(PREAMBLE + """
from repro.core.sharding import TableSpec
from repro.core.embedding import DisaggEmbedding, make_cache_from_table
specs = [TableSpec("a", 1000, nnz=4), TableSpec("b", 500, nnz=2, pooling="mean"),
         TableSpec("c", 64, nnz=1)]
B = 8
idx = np.zeros((B,3,4), np.int32); msk = np.zeros((B,3,4), bool)
for f,s in enumerate(specs):
    idx[:,f,:s.nnz] = rng.integers(0, s.vocab, (B,s.nnz)); msk[:,f,:s.nnz] = True
for mode in ("baseline", "hierarchical"):
    emb = DisaggEmbedding(specs=specs, dim=16, num_shards=4, mode=mode)
    params = emb.init(jax.random.key(0))
    ref = emb.lookup_reference(params, jnp.asarray(idx), jnp.asarray(msk))
    out = jax.jit(lambda p,i,m: emb.lookup(p,i,m,mesh=mesh,num_chunks=2))(params, jnp.asarray(idx), jnp.asarray(msk))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-4, atol=1e-5)
    hot = rng.choice(1000, 64, replace=False)
    cache = make_cache_from_table(emb, params, hot, 64, mesh=mesh)
    out_c = jax.jit(lambda p,i,m,c: emb.lookup(p,i,m,mesh=mesh,cache=c))(params, jnp.asarray(idx), jnp.asarray(msk), cache)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out_c), rtol=1e-4, atol=1e-5)
# gradient parity
emb = DisaggEmbedding(specs=specs, dim=16, num_shards=4)
params = emb.init(jax.random.key(1))
g1 = jax.jit(jax.grad(lambda p: emb.lookup(p, jnp.asarray(idx), jnp.asarray(msk), mesh=mesh).sum()))(params)
g2 = jax.grad(lambda p: emb.lookup_reference(p, jnp.asarray(idx), jnp.asarray(msk)).sum())(params)
np.testing.assert_allclose(np.asarray(g1["table"]), np.asarray(g2["table"]), rtol=1e-4, atol=1e-5)
print("OK")
""")


def test_mesh2d_and_fused_wide_sharded():
    _run(PREAMBLE + """
from repro.core.sharding import TableSpec
from repro.core.embedding import DisaggEmbedding
import repro.models.recsys as R
from repro.data import synthetic as syn
specs = [TableSpec("a", 1000, nnz=4), TableSpec("b", 500, nnz=2, pooling="mean"),
         TableSpec("c", 64, nnz=1)]
B = 16
idx = np.zeros((B,3,4), np.int32); msk = np.zeros((B,3,4), bool)
for f,s in enumerate(specs):
    idx[:,f,:s.nnz] = rng.integers(0, s.vocab, (B,s.nnz)); msk[:,f,:s.nnz] = True
emb = DisaggEmbedding(specs=specs, dim=16, num_shards=8, mode="mesh2d")
params = emb.init(jax.random.key(0))
ref = emb.lookup_reference(params, jnp.asarray(idx), jnp.asarray(msk))
out = jax.jit(lambda p,i,m: emb.lookup(p,i,m,mesh=mesh))(params, jnp.asarray(idx), jnp.asarray(msk))
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)
g1 = jax.jit(jax.grad(lambda p: emb.lookup(p, jnp.asarray(idx), jnp.asarray(msk), mesh=mesh).sum()))(params)
g2 = jax.grad(lambda p: emb.lookup_reference(p, jnp.asarray(idx), jnp.asarray(msk)).sum())(params)
np.testing.assert_allclose(np.asarray(g1["table"]), np.asarray(g2["table"]), rtol=1e-4, atol=1e-5)
# fused-wide wide_deep == separate-wide wide_deep (same table values)
tables = tuple(TableSpec(f"t{i}", 300+31*i, nnz=(4 if i<1 else 1)) for i in range(4))
cfgA = R.RecsysConfig(name="wd", arch="wide_deep", tables=tables, embed_dim=16,
                      n_dense=5, mlp=(32,16), use_wide=True, mode="mesh2d")
cfgB = R.RecsysConfig(name="wdf", arch="wide_deep", tables=tables, embed_dim=16,
                      n_dense=5, mlp=(32,16), use_wide=True, fuse_wide=True, mode="mesh2d")
pA = R.init_params(cfgA, jax.random.key(1), num_shards=8)
pB = R.init_params(cfgB, jax.random.key(1), num_shards=8)
# align values: fused table cols [0:16] = emb, col 16 = wide col 0
tabA = np.asarray(pA["emb"]["table"]); wideA = np.asarray(pA["wide"]["table"])
tabB = np.asarray(pB["emb"]["table"]).copy()
n = min(len(tabA), len(tabB))
tabB[:n, :16] = tabA[:n]; tabB[:n, 16:] = wideA[:n][:, :8]
pB["emb"]["table"] = jnp.asarray(tabB)
b = {k: jnp.asarray(v) for k,v in syn.recsys_batch(rng, tables, 16, n_dense=5).items()}
sA = jax.jit(lambda p,b: R.forward(cfgA, p, b, mesh))(pA, b)
sB = jax.jit(lambda p,b: R.forward(cfgB, p, b, mesh))(pB, b)
np.testing.assert_allclose(np.asarray(sA), np.asarray(sB), rtol=1e-4, atol=1e-4)
print("OK")
""")


def test_partitioned_gnn_sharded():
    _run(PREAMBLE + """
import repro.models.gnn as G
from repro.data import synthetic as syn
N, E = 64, 256
g = syn.random_graph(rng, N, E, 16, 5, power_law=False)
cfg = G.GNNConfig(name="t", d_in=16, d_hidden=8, n_classes=5)
params = G.init_params(cfg, jax.random.key(0))
n_loc = N // 8
shard_of = g["edges"][:, 1] // n_loc
order = np.argsort(shard_of, kind="stable")
edges_p = g["edges"][order]; shard_of = shard_of[order]
cap = max(np.sum(shard_of == s) for s in range(8))
ep = np.zeros((8 * cap, 2), np.int32); mp = np.zeros((8 * cap,), bool)
for s in range(8):
    rows = edges_p[shard_of == s]
    ep[s*cap:s*cap+len(rows)] = rows
    ep[s*cap+len(rows):(s+1)*cap, 1] = s * n_loc
    mp[s*cap:s*cap+len(rows)] = True
out = jax.jit(lambda p, f, e, m: G.forward_full_graph_partitioned(
    cfg, p, f, e, m, mesh, comm_dtype=jnp.float32))(
    params, jnp.asarray(g["feats"]), jnp.asarray(ep), jnp.asarray(mp))
ref = G.forward_full_graph(cfg, params, jnp.asarray(g["feats"]),
                           jnp.asarray(g["edges"]), jnp.asarray(g["edge_mask"]), None)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
print("OK")
""")


def test_transformer_sharded_matches_single():
    _run(PREAMBLE + """
import repro.models.transformer as T
cfg = T.TransformerConfig(name="t", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
                          d_ff=128, vocab=256, d_head=8, compute_dtype=jnp.float32,
                          remat_groups=2, seq_shard=True)
params = T.init_params(cfg, jax.random.key(0), mesh)
toks = jnp.asarray(rng.integers(0, 256, (4, 16)), jnp.int32)
l1, _ = jax.jit(lambda p,t: T.forward(cfg, p, t, mesh))(params, toks)
l2, _ = jax.jit(lambda p,t: T.forward(cfg, p, t, None))(params, toks)
np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-3, atol=2e-3)
# sharded decode vs sharded forward
cache = T.init_decode_cache(cfg, 4, 32, jnp.float32)
lg, _ = jax.jit(lambda p,c,t,pos: T.decode_step(cfg, p, c, t, pos, mesh))(params, cache, toks[:,0], jnp.asarray(0,jnp.int32))
np.testing.assert_allclose(np.asarray(lg[:, :256]), np.asarray(l2[:, 0, :256]), rtol=2e-3, atol=2e-3)
print("OK")
""")


def test_moe_sharded_matches_reference():
    _run(PREAMBLE + """
import repro.models.transformer as T
from repro.models.moe import MoEConfig
cfg = T.TransformerConfig(name="m", n_layers=2, d_model=32, n_heads=4, n_kv_heads=4,
                          d_ff=64, vocab=128, d_head=8, compute_dtype=jnp.float32,
                          remat_groups=2, moe=MoEConfig(num_experts=8, top_k=2, d_ff=32,
                          capacity_factor=8.0), moe_dense_residual=True)
params = T.init_params(cfg, jax.random.key(1), mesh)
toks = jnp.asarray(rng.integers(0, 128, (4, 8)), jnp.int32)
l1, a1 = jax.jit(lambda p,t: T.forward(cfg, p, t, mesh))(params, toks)
l2, a2 = jax.jit(lambda p,t: T.forward(cfg, p, t, None))(params, toks)
np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-3, atol=2e-3)
# aux is the mean of per-data-shard Switch losses (GShard semantics), which
# only approximates the global-batch aux -> loose tolerance
np.testing.assert_allclose(float(a1), float(a2), rtol=0.5)
print("OK")
""")


def test_recsys_and_gnn_sharded():
    _run(PREAMBLE + """
import repro.models.recsys as R
import repro.models.gnn as G
from repro.core.sharding import TableSpec
from repro.data import synthetic as syn
tables = tuple(TableSpec(f"t{i}", 500+97*i, nnz=(4 if i<2 else 1)) for i in range(5))
cfg = R.RecsysConfig(name="d", arch="dlrm", tables=tables, embed_dim=16,
                     n_dense=13, bottom_mlp=(64,16), mlp=(64,32))
params = R.init_params(cfg, jax.random.key(2), num_shards=4)
b = {k: jnp.asarray(v) for k,v in syn.recsys_batch(rng, tables, 16, n_dense=13).items()}
s1 = jax.jit(lambda p,b: R.forward(cfg, p, b, mesh))(params, b)
s2 = jax.jit(lambda p,b: R.forward(cfg, p, b, None))(params, b)
np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=2e-4, atol=2e-4)
g = syn.random_graph(rng, 100, 512, 16, 5)
gcfg = G.GNNConfig(name="s", d_in=16, d_hidden=8, n_classes=5)
gp = G.init_params(gcfg, jax.random.key(3))
o1 = jax.jit(lambda p,f,e,m: G.forward_full_graph(gcfg,p,f,e,m,mesh))(gp, jnp.asarray(g["feats"]), jnp.asarray(g["edges"]), jnp.asarray(g["edge_mask"]))
o2 = G.forward_full_graph(gcfg, gp, jnp.asarray(g["feats"]), jnp.asarray(g["edges"]), jnp.asarray(g["edge_mask"]), None)
np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-4)
print("OK")
""")


def test_retrieval_topk_sharded():
    _run(PREAMBLE + """
import repro.models.recsys as R
from repro.core.sharding import TableSpec
from repro.data import synthetic as syn
tables = tuple(TableSpec(f"t{i}", 400+31*i, nnz=1) for i in range(4))
tt = R.RecsysConfig(name="tt", arch="two_tower", tables=tables, embed_dim=16,
                    user_tables=2, mlp=(64, 32))
tp = R.init_params(tt, jax.random.key(4), num_shards=4)
cand = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
qb = {k: jnp.asarray(v) for k,v in syn.recsys_batch(rng, tables, 8).items()}
val, idx = jax.jit(lambda p,b,c: R.retrieval_topk(tt, p, b, c, k=5, mesh=mesh))(tp, qb, cand)
pooled = tt.embedding(4).lookup_reference(tp["emb"], qb["indices"], qb["mask"])
import repro.models.layers as LL
u = LL.mlp_apply(tp["user_mlp"], pooled[:, :2].reshape(8, -1))
u = u / jnp.linalg.norm(u, axis=-1, keepdims=True)
vref, iref = jax.lax.top_k(u @ cand.T, 5)
np.testing.assert_allclose(np.asarray(val), np.asarray(vref), rtol=1e-4, atol=1e-5)
print("OK")
""")
