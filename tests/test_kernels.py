"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), with
shape/dtype sweeps per the deliverable."""
import warnings

warnings.filterwarnings("ignore")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.attention import blocked_attention


@pytest.mark.parametrize(
    "B,S,H,KVH,D,causal,window,dtype",
    [
        (2, 64, 4, 2, 32, True, 0, jnp.float32),
        (1, 100, 2, 2, 16, True, 9, jnp.float32),
        (2, 128, 4, 1, 64, False, 0, jnp.bfloat16),
        (1, 256, 8, 4, 128, True, 64, jnp.float32),
        (1, 96, 4, 4, 8, True, 0, jnp.bfloat16),
    ],
)
def test_flash_attention_kernel(B, S, H, KVH, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_k=32, interpret=True)
    want = blocked_attention(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_vs_dense():
    """Independent dense (S×S) oracle."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 4, 16))
    v = jax.random.normal(ks[2], (2, 64, 4, 16))
    out = ops.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              interpret=True)
    want = ref.flash_attention_dense_ref(
        q.transpose(0, 2, 1, 3).reshape(8, 64, 16),
        k.transpose(0, 2, 1, 3).reshape(8, 64, 16),
        v.transpose(0, 2, 1, 3).reshape(8, 64, 16), causal=True)
    want = want.reshape(2, 4, 64, 16).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,block,dtype", [
    (1 << 12, 1024, jnp.float32),
    (1 << 14, 4096, jnp.float32),
    (1 << 12, 4096, jnp.bfloat16),
    ((1 << 12) + 5, 4096, jnp.float32),   # unaligned: padded, cut back
])
def test_elastic_update_kernel(n, block, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    w, v, g, c, m = (jax.random.normal(k, (n,), dtype) for k in ks)
    out = ops.elastic_update(w, v, g, c, m, eta=0.1, rho=0.05, mu=0.9,
                             n_workers=4, block=block, interpret=True)
    want = ref.elastic_update_ref(w, v, g, c, m, eta=0.1, rho=0.05, mu=0.9,
                                  n_workers=4)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    for a, b in zip(out, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("BH,S,P,N,L", [
    (4, 128, 32, 64, 32),
    (2, 64, 16, 16, 16),
    (1, 256, 64, 128, 64),
])
def test_ssd_intra_kernel(BH, S, P, N, L):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    a = -jax.nn.softplus(jax.random.normal(ks[0], (BH, S)))
    x = jax.random.normal(ks[1], (BH, S, P))
    b = jax.random.normal(ks[2], (BH, S, N))
    c = jax.random.normal(ks[3], (BH, S, N))
    out = ops.ssd_intra_chunk(a, x, b, c, chunk=L, interpret=True)
    want = ref.ssd_intra_ref(a, x, b, c, chunk=L)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("T,d,V,bt,bv", [
    (64, 32, 300, 16, 128),       # vocab not a multiple of the tile
    (100, 16, 512, 32, 128),      # tokens not a multiple of the tile
    (32, 64, 1000, 32, 256),
])
def test_fused_cross_entropy_kernel(T, d, V, bt, bv):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    h = jax.random.normal(ks[0], (T, d))
    w = jax.random.normal(ks[1], (d, V)) * 0.1
    t = jax.random.randint(ks[2], (T,), 0, V)
    out = ops.fused_cross_entropy(h, w, t, block_t=bt, block_v=bv,
                                  interpret=True)
    want = ref.fused_ce_ref(h, w, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_train_custom_vjp_matches_autodiff():
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    from repro.models.attention import flash_attention_train
    q = jax.random.normal(ks[0], (2, 96, 4, 16))
    k = jax.random.normal(ks[1], (2, 96, 2, 16))
    v = jax.random.normal(ks[2], (2, 96, 2, 16))
    dout = jax.random.normal(ks[3], (2, 96, 4, 16))
    kw = dict(causal=True, window=11, q_block=32, kv_block=16)
    f1 = lambda q, k, v: jnp.sum(flash_attention_train(q, k, v, **kw) * dout)
    f2 = lambda q, k, v: jnp.sum(blocked_attention(q, k, v, causal=True,
                                                   window=11, q_block=32,
                                                   kv_block=16) * dout)
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
