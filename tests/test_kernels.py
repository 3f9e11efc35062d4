"""Pallas kernel sweeps vs the ref.py oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as REF
from repro.kernels.dot_interaction import dot_interaction
from repro.kernels.embedding_bag import embedding_bag, pack_rows
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ops import bag_lookup, dot_interaction_triu


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "V,D,bags,nnz",
    [(64, 128, 4, 1), (200, 128, 16, 4), (512, 256, 8, 8), (101, 64, 13, 4),
     (50, 32, 7, 3)],
)
def test_embedding_bag_sweep(dtype, V, D, bags, nnz, rng):
    table = jnp.asarray(rng.normal(size=(V, D)), dtype)
    idx = jnp.asarray(rng.integers(0, V, bags * nnz).astype(np.int32))
    w = jnp.asarray((rng.random(bags * nnz) > 0.25).astype(np.float32))
    out = embedding_bag(pack_rows(table), idx, w, bags, dim=D, interpret=True)
    want = REF.embedding_bag_ref(table, idx, w, bags)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=tol, atol=tol)


def _check_bag_lookup_wrapper(D, rng):
    table = jnp.asarray(rng.normal(size=(100, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 100, (4, 3, 2)).astype(np.int32))
    msk = jnp.asarray(rng.random((4, 3, 2)) > 0.3)
    out = bag_lookup(table, idx, msk, interpret=True)
    rows = np.asarray(table)[np.asarray(idx)] * np.asarray(msk)[..., None]
    np.testing.assert_allclose(np.asarray(out), rows.sum(axis=2), rtol=1e-5, atol=1e-5)


def test_bag_lookup_wrapper(rng):
    _check_bag_lookup_wrapper(128, rng)


def test_bag_lookup_wrapper_packed_lines(rng):
    _check_bag_lookup_wrapper(64, rng)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,F,D,blk", [(8, 7, 32, 4), (16, 27, 64, 8), (4, 40, 16, 4)])
def test_dot_interaction_sweep(dtype, B, F, D, blk, rng):
    x = jnp.asarray(rng.normal(size=(B, F, D)), dtype)
    out = dot_interaction(x, block_b=blk, interpret=True)
    want = REF.dot_interaction_ref(x)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=tol, atol=tol)


def test_dot_interaction_triu(rng):
    x = jnp.asarray(rng.normal(size=(4, 5, 16)).astype(np.float32))
    out = dot_interaction_triu(x, interpret=True)
    assert out.shape == (4, 15)
    full = np.einsum("bfd,bgd->bfg", np.asarray(x), np.asarray(x))
    iu, ju = np.triu_indices(5)
    np.testing.assert_allclose(np.asarray(out), full[:, iu, ju], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,Hkv,dh,causal,bq,bk",
    [
        (2, 64, 4, 2, 16, True, 32, 32),
        (1, 128, 4, 4, 32, False, 64, 32),
        (2, 64, 8, 2, 64, True, 16, 64),
        (1, 256, 2, 1, 128, True, 128, 128),
    ],
)
def test_flash_attention_sweep(dtype, B, S, H, Hkv, dh, causal, bq, bk, rng):
    q = jnp.asarray(rng.normal(size=(B, S, H, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    want = REF.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,Hkv,dh,L,bk",
    [(2, 128, 8, 2, 16, 100, 32), (1, 256, 4, 4, 32, 256, 64),
     (2, 64, 16, 2, 64, 1, 32), (1, 128, 2, 1, 128, 77, 128)],
)
def test_flash_decode_sweep(dtype, B, S, H, Hkv, dh, L, bk, rng):
    """flash_decode kernel vs the model-path flash_decode_shard oracle."""
    from repro.kernels.flash_decode import flash_decode
    from repro.models.layers import flash_decode_shard

    q = jnp.asarray(rng.normal(size=(B, H, dh)), dtype)
    kc = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), dtype)
    vc = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), dtype)
    out = flash_decode(q, kc, vc, jnp.asarray(L, jnp.int32), block_k=bk,
                       interpret=True)
    ref = flash_decode_shard(q, kc, vc, jnp.asarray(L, jnp.int32),
                             jnp.zeros((), jnp.int32), combine_axes=())
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_matches_model_attention(rng):
    """Kernel vs the XLA-path attention used by the transformer models."""
    from repro.models.layers import gqa_prefill_attention

    q = jnp.asarray(rng.normal(size=(2, 64, 4, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 64, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 64, 2, 16)).astype(np.float32))
    a = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    b = gqa_prefill_attention(q, k, v, causal=True, q_block=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)
