"""End-to-end training driver (CPU-runnable; mesh-ready).

Examples:
  PYTHONPATH=src python -m repro.launch.train --model dlrm --steps 200
  PYTHONPATH=src python -m repro.launch.train --model lm --steps 50
  PYTHONPATH=src python -m repro.launch.train --model dlrm --steps 40 \
      --resume --ckpt-dir /tmp/ck   # kill it mid-run, rerun: it restarts

Features exercised: synthetic zipf pipeline with prefetch, composite
optimizer (rowwise adagrad + adam), async sharded checkpointing with restart,
elastic embedding-tier resharding (--reshard-at), loss logging.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import CheckpointManager
from repro.core.sharding import TableSpec
from repro.data import synthetic as syn
from repro.data.pipeline import PrefetchIterator
from repro.models import recsys as R
from repro.models import transformer as T
from repro.optim import optimizers as opt_lib
from repro.runtime.elastic import reshard_params
from repro.utils import enable_compile_cache, logger, tree_num_params


def make_dlrm_100m() -> R.RecsysConfig:
    """~100M-parameter DLRM (example-scale version of dlrm-flexemr)."""
    tables = (
        [TableSpec(f"big_{i}", 300_000, nnz=4) for i in range(2)]
        + [TableSpec(f"mid_{i}", 80_000, nnz=1) for i in range(8)]
        + [TableSpec(f"small_{i}", 2_000, nnz=1) for i in range(16)]
    )
    return R.RecsysConfig(
        name="dlrm-100m",
        arch="dlrm",
        tables=tuple(tables),
        embed_dim=64,
        n_dense=13,
        bottom_mlp=(512, 256, 64),
        mlp=(512, 256),
    )


def make_lm_small() -> T.TransformerConfig:
    return T.TransformerConfig(
        name="lm-small",
        n_layers=4,
        d_model=256,
        n_heads=8,
        n_kv_heads=4,
        d_ff=1024,
        vocab=8192,
        d_head=32,
        compute_dtype=jnp.float32,
        remat_groups=2,
    )


def train_recsys(args) -> dict:
    cfg = make_dlrm_100m()
    rng = np.random.default_rng(args.seed)
    optimizer = opt_lib.make_composite(
        [("emb", opt_lib.make_rowwise_adagrad(0.05)), (".*", opt_lib.make_adam(1e-3))]
    )
    params = R.init_params(cfg, jax.random.key(args.seed))
    logger.info("dlrm params: %.1fM", tree_num_params(params) / 1e6)
    state = optimizer.init(params)
    start_step = 0
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt and args.resume and ckpt.latest_step() is not None:
        (params, state), extra = ckpt.restore((params, state))
        start_step = extra["step"] + 1
        logger.info("resumed from step %d", start_step)

    def make_batch(step):
        r = np.random.default_rng(args.seed * 100_003 + step)
        return {
            k: jnp.asarray(v)
            for k, v in syn.recsys_batch(
                r, cfg.tables, args.batch, n_dense=cfg.n_dense
            ).items()
        }

    it = PrefetchIterator(make_batch, start_step)
    step_fn = jax.jit(R.make_train_step(cfg, optimizer, None))
    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = next(it)
        params, state, metrics = step_fn(params, state, batch)
        if args.reshard_at and step == args.reshard_at:
            emb = cfg.embedding(1)
            tables, new_emb = reshard_params(emb.sharded, params["emb"], 4)
            logger.info("elastic reshard 1 -> 4 embedding servers: %s rows",
                        tables.total_rows)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0:
            logger.info("step %d loss %.4f (%.2f s/step)", step, loss,
                        (time.time() - t0) / max(1, step - start_step + 1))
        if ckpt and step % args.ckpt_every == 0 and step > start_step:
            ckpt.save(step, (params, state), extra={"step": step})
    it.close()
    if ckpt:
        ckpt.save(args.steps - 1, (params, state), extra={"step": args.steps - 1},
                  blocking=True)
    return {"final_loss": losses[-1], "first_loss": losses[0]}


def train_lm(args) -> dict:
    cfg = make_lm_small()
    optimizer = opt_lib.make_adam(3e-4)
    params = T.init_params(cfg, jax.random.key(args.seed))
    logger.info("lm params: %.1fM", tree_num_params(params) / 1e6)
    state = optimizer.init(params)

    def make_batch(step):
        r = np.random.default_rng(args.seed * 999 + step)
        return {k: jnp.asarray(v) for k, v in syn.lm_batch(r, cfg.vocab, args.batch, args.seq).items()}

    it = PrefetchIterator(make_batch, 0)
    step_fn = jax.jit(T.make_train_step(cfg, optimizer, None))
    losses = []
    for step in range(args.steps):
        params, state, metrics = step_fn(params, state, next(it))
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            logger.info("step %d loss %.4f", step, losses[-1])
    it.close()
    return {"final_loss": losses[-1], "first_loss": losses[0]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["dlrm", "lm"], default="dlrm")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--reshard-at", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()
    out = train_recsys(args) if args.model == "dlrm" else train_lm(args)
    logger.info("done: %s", out)
    assert out["final_loss"] < out["first_loss"], "loss must improve"


if __name__ == "__main__":
    main()
