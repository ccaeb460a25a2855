"""Pure-jnp oracles, independent of the program (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_dense_ref(q, k, v, *, causal=True, window=0):
    """Direct dense (S×S) reference — independent of the blocked code."""
    BH, S, D = q.shape
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(D))
    i = jnp.arange(S)
    m = jnp.ones((S, S), bool)
    if causal:
        m &= i[:, None] >= i[None, :]
    if window:
        m &= i[:, None] - i[None, :] < window
    s = jnp.where(m[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_intra_ref(a, x, b, c, *, chunk: int):
    """Intra-chunk SSD, per chunk and head h:
    Y[i] = Σ_{j≤i} (C_i·B_j) e^{cum_i−cum_j} X_j.
    a: (R, S, H) log-decay; x: (R, S, H, P); b, c: (R, S, N), one group
    shared by every head. The exponent is masked before exp (above the
    diagonal it can pass float32's range; 0·inf is NaN in a gradient)."""
    R, S, H, P = x.shape
    L = min(chunk, S)
    nc = S // L
    a_ = a.reshape(R, nc, L, H).astype(jnp.float32)
    x_ = x.reshape(R, nc, L, H, P).astype(jnp.float32)
    b_ = b.reshape(R, nc, L, -1).astype(jnp.float32)
    c_ = c.reshape(R, nc, L, -1).astype(jnp.float32)
    cum = jnp.cumsum(a_, axis=2)                          # (R, nc, L, H)
    g = jnp.einsum("rcln,rcmn->rclm", c_, b_)
    mask = jnp.tril(jnp.ones((L, L), bool))[None, None, :, :, None]
    dec = jnp.exp(jnp.where(mask, cum[:, :, :, None] - cum[:, :, None],
                            -jnp.inf))                    # (R, nc, L, L, H)
    y = jnp.einsum("rclm,rclmh,rcmhp->rclhp", g, dec, x_)
    return y.reshape(R, S, H, P).astype(x.dtype)
