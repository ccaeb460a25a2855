"""phi3-mini: the plain reference of its loss, its leaves and its model
FLOPs per token, read from ``phi3_mini.json`` beside this file.

A pre-norm decoder of ``n_layers`` identical layers: RMSNorm, multi-head
attention (rotary embedding on the two halves of each head, causal,
scale 1/sqrt(head_dim)), residual, RMSNorm, SwiGLU MLP, residual; a final
RMSNorm and an untied unembedding. RMSNorm is x / sqrt(mean(x^2) + 1e-6)
* (1 + gamma). Departures from the published model, which the program
shares, are listed in the JSON file under ``departures``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS_PER_BLOCK = 1          # sequences per reference block
HEADS_PER_BLOCK = 8         # attention heads per checkpointed block


def layout(m):
    """The leaves, in the order the program flattens its parameters, with
    their shapes and stated initialisation."""
    L, d, H, K, D = (m["n_layers"], m["d_model"], m["n_heads"],
                     m["n_kv_heads"], m["head_dim"])
    f, V = m["d_ff"], m["vocab_size"]
    return [
        ("blocks.attn.wk", (L, d, K, D), "normal"),
        ("blocks.attn.wo", (L, H, D, d), "normal"),
        ("blocks.attn.wq", (L, d, H, D), "normal"),
        ("blocks.attn.wv", (L, d, K, D), "normal"),
        ("blocks.ffn.w_down", (L, f, d), "normal"),
        ("blocks.ffn.w_gate", (L, d, f), "normal"),
        ("blocks.ffn.w_up", (L, d, f), "normal"),
        ("blocks.norm1", (L, d), "zeros"),
        ("blocks.norm2", (L, d), "zeros"),
        ("embed", (V, d), "normal"),
        ("final_norm", (d,), "zeros"),
        ("unembed", (d, V), "normal"),
    ]


def flops_per_token(m, seq):
    """Forward and backward FLOPs per token, without recomputation: 6 per
    matmul weight, plus QK^T and AV over the causal half of ``seq``."""
    d, H, K, D = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    per_layer = d * H * D * 2 + d * K * D * 2 + 3 * d * m["d_ff"]
    matmul = m["n_layers"] * per_layer + d * m["vocab_size"]
    attention = m["n_layers"] * 2 * 2 * (seq / 2) * H * D   # fwd QK^T + AV
    return 6 * matmul + 3 * attention


def _rms(x, gamma):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * (1.0 + gamma)


def _rope(x, theta):
    S, D = x.shape[-3], x.shape[-1]
    half = D // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv     # (S, half)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, prec):
    """Causal softmax attention of one sequence, in blocks of heads that
    the backward pass recomputes. q, k, v: (S, H, D)."""
    S, H, D = q.shape
    hb = math.gcd(H, HEADS_PER_BLOCK)
    n = H // hb
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def block(qkv):
        qb, kb, vb = qkv
        s = prec.mm("qhd,khd->hqk", qb, kb, wide=True) / math.sqrt(D)
        s = jnp.where(causal[None], s, -jnp.inf)
        return prec.mm("hqk,khd->qhd", jax.nn.softmax(s, -1), vb, wide=True)

    split = lambda t: t.reshape(S, n, hb, D).swapaxes(0, 1)
    out = jax.lax.map(block, (split(q), split(k), split(v)))
    return out.swapaxes(0, 1).reshape(S, H, D)


def loss_sum(m, p, tokens, targets, mask, prec):
    """Sum over the rows of the masked next-token cross-entropy. ``prec``
    (``reference.Precision``) gives the matmuls and rounds each activation
    that the program keeps in its compute dtype."""
    theta, rep = m["rope_theta"], m["n_heads"] // m["n_kv_heads"]
    mm, c = prec.mm, prec.cast
    total = jnp.zeros((), jnp.float32)
    for b in range(tokens.shape[0]):
        x = c(p["embed"][tokens[b]])                             # (S, d)
        for l in range(m["n_layers"]):
            h = c(_rms(x, p["blocks.norm1"][l]))
            q = c(_rope(mm("sd,dhk->shk", h, p["blocks.attn.wq"][l]), theta))
            k = c(_rope(mm("sd,dhk->shk", h, p["blocks.attn.wk"][l]), theta))
            v = mm("sd,dhk->shk", h, p["blocks.attn.wv"][l])
            k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
            a = c(_attention(q, k, v, prec))
            x = c(x + mm("shk,hkd->sd", a, p["blocks.attn.wo"][l]))
            h = c(_rms(x, p["blocks.norm2"][l]))
            g = c(jax.nn.silu(mm("sd,df->sf", h, p["blocks.ffn.w_gate"][l])))
            u = mm("sd,df->sf", h, p["blocks.ffn.w_up"][l])
            x = c(x + mm("sf,fd->sd", c(g * u), p["blocks.ffn.w_down"][l]))
        logits = mm("sd,dv->sv", c(_rms(x, p["final_norm"])), p["unembed"],
                    wide=True)
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, targets[b][:, None], -1)[:, 0]
        total = total + jnp.sum(ce * mask[b])
    return total
