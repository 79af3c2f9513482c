"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode on CPU).

Shapes/dtypes swept per the deliverable spec; gradients checked through the
custom VJPs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.fused_infonce.ops import fused_infonce_loss, fused_infonce_rows
from repro.kernels.fused_infonce.ref import (
    infonce_grads_ref,
    infonce_loss_ref,
    infonce_rows_ref,
)


# ---------------------------------------------------------------- fused infonce
@pytest.mark.parametrize(
    "m,n,d,bm,bn",
    [
        (128, 128, 32, 128, 128),
        (256, 384, 64, 128, 128),
        (64, 192, 16, 32, 64),     # sub-MXU blocks still correct
        (512, 512, 128, 128, 256),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_infonce_fwd_sweep(m, n, d, bm, bn, dtype):
    ks = jax.random.split(jax.random.PRNGKey(m + n), 3)
    q = jax.random.normal(ks[0], (m, d), dtype)
    p = jax.random.normal(ks[1], (n, d), dtype)
    labels = jax.random.randint(ks[2], (m,), 0, n)
    lse, pos = fused_infonce_rows(q, p, labels, 1.3, bm, bn, True)
    lse_r, pos_r = infonce_rows_ref(q, p, labels, inv_tau=1.3)
    rtol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r), rtol=rtol)
    np.testing.assert_allclose(np.asarray(pos), np.asarray(pos_r), rtol=rtol, atol=1e-6)


@pytest.mark.parametrize("m,n,d", [(128, 256, 32), (256, 256, 64)])
def test_fused_infonce_grads_match_oracle(m, n, d):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (m, d))
    p = jax.random.normal(ks[1], (n, d))
    labels = jax.random.randint(ks[2], (m,), 0, n)
    gq, gp = jax.grad(
        lambda q_, p_: fused_infonce_loss(q_, p_, labels, temperature=0.7,
                                           interpret=True),
        argnums=(0, 1),
    )(q, p)
    gq_r, gp_r = infonce_grads_ref(q, p, labels, inv_tau=1.0 / 0.7)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gq_r), rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gp_r), rtol=1e-4, atol=1e-7)


def test_fused_infonce_loss_value_jit():
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(ks[0], (128, 32))
    p = jax.random.normal(ks[1], (128, 32))
    loss = jax.jit(lambda a, b: fused_infonce_loss(a, b, interpret=True))(q, p)
    loss_r = infonce_loss_ref(q, p, jnp.arange(128, dtype=jnp.int32))
    np.testing.assert_allclose(float(loss), float(loss_r), rtol=1e-6)


def test_fused_infonce_weighted_row_cotangents():
    """Generalized VJP: arbitrary per-row weights (masked bank rows etc.)."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    m, n, d = 128, 128, 32
    q = jax.random.normal(ks[0], (m, d))
    p = jax.random.normal(ks[1], (n, d))
    labels = jnp.arange(m, dtype=jnp.int32)
    w = jax.random.uniform(ks[2], (m,))

    def loss_k(q_, p_):
        lse, pos = fused_infonce_rows(q_, p_, labels, 1.0, 128, 128, True)
        return jnp.sum((lse - pos) * w)

    def loss_r(q_, p_):
        lse, pos = infonce_rows_ref(q_, p_, labels)
        return jnp.sum((lse - pos) * w)

    gk = jax.grad(loss_k, argnums=(0, 1))(q, p)
    gr = jax.grad(loss_r, argnums=(0, 1))(q, p)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-6)


# ---------------------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "b,sq,skv,h,hk,d,causal",
    [
        (2, 128, 128, 4, 4, 32, False),
        (2, 128, 128, 4, 4, 32, True),
        (1, 256, 256, 8, 2, 64, True),    # GQA 4:1
        (2, 64, 256, 4, 1, 32, False),    # MQA cross-length
    ],
)
def test_flash_attention_fwd_sweep(b, sq, skv, h, hk, d, causal):
    ks = jax.random.split(jax.random.PRNGKey(sq + skv + h), 4)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, skv, hk, d))
    v = jax.random.normal(ks[2], (b, skv, hk, d))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kv_mask_and_dtype(dtype):
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    b, s, h, d = 2, 128, 4, 32
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, h, d), dtype)
    v = jax.random.normal(ks[2], (b, s, h, d), dtype)
    mask = jax.random.bernoulli(ks[3], 0.7, (b, s)).at[:, 0].set(True)
    out = flash_attention(q, k, v, kv_mask=mask, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, kv_mask=mask)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_grads_match_plain():
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    b, s, h, d = 1, 128, 2, 32
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))

    def f_kernel(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, block_q=64, block_k=64,
                               interpret=True).sum()

    def f_ref(q_, k_, v_):
        return flash_attention_ref(q_, k_, v_, causal=True).sum()

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------- embedding bag
@pytest.mark.parametrize(
    "v,d,l,n_bags",
    [(64, 128, 32, 8), (256, 128, 100, 10), (1000, 256, 17, 5)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_sweep(v, d, l, n_bags, dtype):
    ks = jax.random.split(jax.random.PRNGKey(v + l), 3)
    table = jax.random.normal(ks[0], (v, d), dtype)
    indices = jax.random.randint(ks[1], (l,), 0, v)
    # sorted non-decreasing bag ids covering all bags
    bag_ids = jnp.sort(jax.random.randint(ks[2], (l,), 0, n_bags))
    out = embedding_bag(table, indices, bag_ids, n_bags, True)
    ref = embedding_bag_ref(table, indices, bag_ids, n_bags)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_embedding_bag_grad_scatter():
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    v, d, l, n_bags = 32, 128, 16, 4
    table = jax.random.normal(ks[0], (v, d))
    indices = jax.random.randint(ks[1], (l,), 0, v)
    bag_ids = jnp.sort(jax.random.randint(ks[2], (l,), 0, n_bags))

    def f_kernel(t):
        return (embedding_bag(t, indices, bag_ids, n_bags, True) ** 2).sum()

    def f_ref(t):
        return (embedding_bag_ref(t, indices, bag_ids, n_bags) ** 2).sum()

    gk = jax.grad(f_kernel)(table)
    gr = jax.grad(f_ref)(table)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), rtol=1e-5, atol=1e-6)


def test_embedding_bag_empty_bags_are_zero():
    table = jnp.ones((8, 128))
    indices = jnp.array([0, 1], jnp.int32)
    bag_ids = jnp.array([0, 3], jnp.int32)  # bags 1, 2 empty
    out = embedding_bag(table, indices, bag_ids, 4, True)
    np.testing.assert_array_equal(np.asarray(out[1]), np.zeros(128))
    np.testing.assert_array_equal(np.asarray(out[2]), np.zeros(128))
