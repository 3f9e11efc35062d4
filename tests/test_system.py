"""End-to-end behaviour: training convergence, checkpoint restart continuity,
the serving loop, and config-registry integrity."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.adaptive_cache import AdaptiveCacheController, MemoryModel
from repro.core.sharding import TableSpec, make_fused_tables
from repro.data import synthetic as syn
from repro.models import recsys as R
from repro.optim import optimizers as O
from repro.runtime.serving import FlexEMRServer


def _tiny_dlrm():
    tables = (
        TableSpec("big", 4000, nnz=4),
        TableSpec("mid", 1000, nnz=1),
        TableSpec("small", 64, nnz=1),
    )
    return R.RecsysConfig(
        name="t", arch="dlrm", tables=tables, embed_dim=16, n_dense=13,
        bottom_mlp=(64, 16), mlp=(64, 32),
    )


def test_registry_complete():
    assert set(configs.ASSIGNED).issubset(set(configs.list_archs()))
    assert len(configs.ASSIGNED) == 10
    total_cells = sum(len(configs.get(a).shapes) for a in configs.ASSIGNED)
    assert total_cells == 40


def test_cell_builds_are_structured():
    """Every (arch x shape) build produces matching args/shardings trees
    (uses the production 16x16 mesh abstractly — no device allocation)."""
    from repro.launch.mesh import abstract_mesh

    mesh = abstract_mesh((16, 16), ("data", "model"))
    for arch_id in configs.ASSIGNED:
        arch = configs.get(arch_id)
        for shape in arch.shapes:
            build = arch.build_cell(shape, mesh, False)
            args_leaves = len(jax.tree_util.tree_leaves(build.args))
            spec_leaves = len(
                jax.tree_util.tree_leaves(
                    build.in_shardings,
                    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
                )
            )
            assert args_leaves == spec_leaves, (arch_id, shape)


def test_dlrm_trains_and_restarts(tmp_path, rng):
    from repro.ckpt.checkpoint import CheckpointManager

    cfg = _tiny_dlrm()
    opt = O.make_composite(
        [("emb", O.make_rowwise_adagrad(0.05)), (".*", O.make_adam(1e-3))]
    )
    params = R.init_params(cfg, jax.random.key(0))
    state = opt.init(params)
    step = jax.jit(R.make_train_step(cfg, opt, None))
    mgr = CheckpointManager(tmp_path)

    def batch_at(s):
        # two alternating fixed batches: learnable (loss must descend) while
        # still exercising data-dependent replay determinism after restart
        r = np.random.default_rng(s % 2)
        return {k: jnp.asarray(v) for k, v in
                syn.recsys_batch(r, cfg.tables, 64, n_dense=13).items()}

    losses = []
    for s in range(12):
        params, state, m = step(params, state, batch_at(s))
        losses.append(float(m["loss"]))
        if s == 5:
            mgr.save(s, (params, state), extra={"step": s}, blocking=True)
    assert losses[-1] < losses[0]

    # restart from step 5 and replay -> identical trajectory
    template = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (params, state)
    )
    (p2, s2), extra = mgr.restore(template)
    assert extra["step"] == 5
    for s in range(6, 12):
        p2, s2, m2 = step(p2, s2, batch_at(s))
    np.testing.assert_allclose(float(m2["loss"]), losses[-1], rtol=1e-5)


def test_two_tower_in_batch_softmax_descends(rng):
    tables = (TableSpec("u", 2000, nnz=1), TableSpec("ug", 50, nnz=1),
              TableSpec("i", 3000, nnz=1), TableSpec("ic", 20, nnz=1))
    cfg = R.RecsysConfig(name="tt", arch="two_tower", tables=tables,
                         embed_dim=16, user_tables=2, mlp=(64, 32))
    opt = O.make_adam(1e-3)
    params = R.init_params(cfg, jax.random.key(1))
    state = opt.init(params)
    step = jax.jit(R.make_train_step(cfg, opt, None))
    batch = {k: jnp.asarray(v) for k, v in syn.recsys_batch(rng, tables, 32).items()}
    losses = []
    for _ in range(10):
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_serving_end_to_end(rng):
    cfg = _tiny_dlrm()
    params = R.init_params(cfg, jax.random.key(2))
    tables = make_fused_tables(cfg.tables, cfg.embed_dim, 4)
    controller = AdaptiveCacheController(
        cfg.tables, cfg.embed_dim,
        MemoryModel(fixed_bytes=1 << 20, bytes_per_sample=1 << 10, hbm_bytes=1 << 28),
        field_replication=False, max_rows=1024,
    )
    server = FlexEMRServer(cfg, params, tables, controller=controller,
                           cache_refresh_every=2)
    try:
        for _ in range(40):
            b = syn.recsys_batch(rng, cfg.tables, 1, n_dense=13)
            server.submit({"indices": b["indices"][0], "mask": b["mask"][0],
                           "dense": b["dense"][0]})
        served = 0
        while served < 40:
            out = server.step()
            if out is None:
                continue
            served = server.metrics.requests
            assert np.all(np.isfinite(out["scores"]))
        summ = server.metrics.summary()
        assert summ["requests"] == 40
        # scores equal the plain jit forward (disaggregation is transparent)
        b = syn.recsys_batch(rng, cfg.tables, 4, n_dense=13)
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        want = np.asarray(R.forward(cfg, params, jb, None))
        pooled = server._lookup(b["indices"], b["mask"])
        got = np.asarray(server._dense(jnp.asarray(pooled), jnp.asarray(b["dense"])))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    finally:
        server.close()


def test_train_driver_smoke():
    from repro.launch.train import train_lm

    args = argparse.Namespace(steps=6, batch=8, seq=16, seed=0, log_every=5)
    out = train_lm(args)
    assert out["final_loss"] < out["first_loss"]
