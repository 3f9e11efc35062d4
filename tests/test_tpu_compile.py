"""Compile the served dense step and every Pallas kernel for a described TPU
v5e chip — no chip attached — at dlrm-flexemr serving widths.

What the chip's compiler refuses (a block shape off its tiling, a program
over its memory) fails here at no chip time.  Nothing runs, so these tests
say nothing about results or times.  The topology is described only inside
the module fixture: the TPU library may be loaded by one process at a time,
and every test worker imports this file.
"""
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import dlrm_flexemr
from repro.hotcache.kernels import probe_gather_pool, scatter_update
from repro.hotcache.table import HashCacheState
from repro.kernels.dot_interaction import dot_interaction
from repro.kernels.embedding_bag import embedding_bag
from repro.models import recsys as R
from repro.prefetch.kernels import topk_neighbor_select
from repro.runtime.serving import FlexEMRServer

D, F, BATCH, NNZ = 64, 26, 1024, 4  # dlrm-flexemr widths, largest bucket
BAGS = BATCH * F
N = BAGS * NNZ


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_temp(compiled) -> int:
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel itself
    return compiled.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("bucket", [32, 1024])
def test_served_dense_step_compiles(one_chip, bucket):
    """FlexEMRServer's dense stage at dlrm-flexemr widths, params passed as
    arguments rather than closed over (the same ops)."""
    cfg = dlrm_flexemr.make_config()
    params = R.abstract_params(cfg)
    dense_params = jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        {"bottom": params["bottom"], "top": params["top"]},
    )

    def step(p, pooled, dense):
        server = types.SimpleNamespace(cfg=cfg, params=p)
        return FlexEMRServer._dense_fn(server, pooled, dense)

    compiled = _compile(
        step, dense_params,
        _shape(one_chip, (bucket, cfg.num_fields, cfg.embed_dim)),
        _shape(one_chip, (bucket, cfg.n_dense)),
    )
    assert compiled.out_info.shape == (bucket,)


def test_probe_gather_pool_compiles_without_table_temp(one_chip):
    def temp(C):
        cache = HashCacheState(
            keys=_shape(one_chip, (C,), jnp.int32),
            rows=_shape(one_chip, (C * D // 128, 128)),
            freq=_shape(one_chip, (C,), jnp.int32),
            dim=D,
        )
        return _kernel_temp(_compile(
            lambda c, i, w: probe_gather_pool(c, i, w, num_bags=BAGS),
            cache, _shape(one_chip, (N,), jnp.int32), _shape(one_chip, (N,)),
        ))

    assert temp(1 << 16) == temp(1 << 18)  # nothing scales with the cache


def test_scatter_update_compiles_without_table_temp(one_chip):
    K = 4096

    def temp(C):
        return _kernel_temp(_compile(
            scatter_update, _shape(one_chip, (C * D // 128, 128)),
            _shape(one_chip, (K,), jnp.int32), _shape(one_chip, (K, D)),
        ))

    assert temp(1 << 16) == temp(1 << 18)


def test_embedding_bag_compiles_without_table_temp(one_chip):
    def temp(V):
        return _kernel_temp(_compile(
            lambda t, i, w: embedding_bag(t, i, w, num_bags=BAGS, dim=D),
            _shape(one_chip, (V * D // 128, 128)),
            _shape(one_chip, (N,), jnp.int32), _shape(one_chip, (N,)),
        ))

    assert temp(1 << 20) == temp(1 << 22)


def test_topk_neighbor_select_compiles(one_chip):
    compiled = _compile(
        lambda s: topk_neighbor_select(s, 16), _shape(one_chip, (4096, 200))
    )
    assert _kernel_temp(compiled) < 4096 * 256 * 4


def test_dot_interaction_compiles(one_chip):
    compiled = _compile(dot_interaction, _shape(one_chip, (BATCH, F + 1, D)))
    assert _kernel_temp(compiled) < BATCH * (F + 1) * D * 4
