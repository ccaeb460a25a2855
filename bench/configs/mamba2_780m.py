"""mamba2-780m: the plain reference of its loss, its leaves and its model
FLOPs per token, read from ``mamba2_780m.json`` beside this file.

A pre-norm stack of Mamba-2 blocks (arXiv:2405.21060, section 7): RMSNorm,
an input projection to [z | x | B | C | dt], a depthwise causal
convolution of width ``d_conv`` with bias over [x | B | C] and SiLU, the
selective state-space recurrence with scalar decay per head,

    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t x_t^T,    y_t = C_t h_t + D x_t,

with dt = softplus(dt + dt_bias) and A = -exp(A_log), one group of B and
C, then y * SiLU(z), an RMSNorm with scale (1 + norm), the output
projection and the residual. A final RMSNorm and the tied unembedding.
The recurrence is computed in the paper's chunked form ("SSD minimal":
the quadratic form inside each chunk, the state passed between chunks),
in float32, which is the same sum as the step-by-step recurrence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS_PER_BLOCK = 1          # sequences per reference block


def _dims(m):
    s = m["ssm"]
    d_inner = s["expand"] * m["d_model"]
    return d_inner, d_inner // s["head_dim"], d_inner + 2 * s["d_state"]


def layout(m):
    """The leaves, in the order the program flattens its parameters, with
    their shapes and stated initialisation."""
    s, L, d, V = m["ssm"], m["n_layers"], m["d_model"], m["vocab_size"]
    d_inner, H, conv_dim = _dims(m)
    return [
        ("blocks.norm1", (L, d), "zeros"),
        ("blocks.ssm.A_log", (L, H), "zeros"),
        ("blocks.ssm.D", (L, H), "ones"),
        ("blocks.ssm.conv_b", (L, conv_dim), "zeros"),
        ("blocks.ssm.conv_w", (L, s["d_conv"], conv_dim), "normal"),
        ("blocks.ssm.dt_bias", (L, H), "zeros"),
        ("blocks.ssm.norm", (L, d_inner), "zeros"),
        ("blocks.ssm.w_in", (L, d, 2 * d_inner + 2 * s["d_state"] + H),
         "normal"),
        ("blocks.ssm.w_out", (L, d_inner, d), "normal"),
        ("embed", (V, d), "normal"),
        ("final_norm", (d,), "zeros"),
    ]


def flops_per_token(m, seq):
    """Forward and backward FLOPs per token, without recomputation: 6 per
    matmul or convolution weight (the unembedding is the tied embedding),
    plus 3 times the chunked scan's forward einsums: C B^T and the
    intra-chunk product over the causal half of a chunk, and the state's
    write (B x^T) and read (C h)."""
    s, d = m["ssm"], m["d_model"]
    d_inner, H, conv_dim = _dims(m)
    N, P, L = s["d_state"], s["head_dim"], min(s["chunk"], seq)
    per_layer = (d * (2 * d_inner + 2 * N + H) + s["d_conv"] * conv_dim
                 + d_inner * d)
    matmul = m["n_layers"] * per_layer + d * m["vocab_size"]
    scan = m["n_layers"] * (L * N + L * P * H + 4 * N * P * H)
    return 6 * matmul + 3 * scan


def _rms(x, gamma):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * (1.0 + gamma)


def _segsum(x):
    """x (..., T) -> (..., T, T): sum of x over (j, i] where j <= i, else
    -inf."""
    T = x.shape[-1]
    c = jnp.cumsum(x, -1)
    s = c[..., :, None] - c[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def _ein(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _ssd(X, a, B, C, chunk):
    """The chunked scan of one sequence. X (S, H, P) = dt * x,
    a (S, H) = dt * A, B and C (S, N). Returns y (S, H, P) without D."""
    S, H, P = X.shape
    c = S // chunk
    X = X.reshape(c, chunk, H, P)
    B, C = B.reshape(c, chunk, -1), C.reshape(c, chunk, -1)
    a = a.reshape(c, chunk, H).transpose(2, 0, 1)                # (H, c, l)
    cum = jnp.cumsum(a, -1)
    # inside each chunk
    G = _ein("cln,csn->cls", C, B)                                 # (c, l, l)
    M = G[None] * jnp.exp(_segsum(a))                            # (H,c,l,l)
    y_diag = _ein("hcls,cshp->clhp", M, X)
    # each chunk's own state, then the states passed between chunks
    decay = jnp.exp(cum[..., -1:] - cum)                         # (H, c, l)
    states = _ein("csn,cshp->chpn", B,
                  X * decay.transpose(1, 2, 0)[..., None])
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states], 0)
    pad = jnp.pad(cum[..., -1], ((0, 0), (1, 0)))                # (H, c+1)
    passed = _ein("hzc,chpn->zhpn", jnp.exp(_segsum(pad)), states)[:-1]
    y_off = _ein("cln,chpn->clhp", C, passed) \
        * jnp.exp(cum).transpose(1, 2, 0)[..., None]
    return (y_diag + y_off).reshape(S, H, P)


def _block(m, p, x, prec):
    s = m["ssm"]
    d_inner, H, _ = _dims(m)
    N, Pd, K = s["d_state"], s["head_dim"], s["d_conv"]
    S = x.shape[0]
    c = prec.cast
    h = prec.mm("sd,de->se", c(_rms(x, p["norm1"])), p["w_in"])
    z, xbc, dt = (h[:, :d_inner], h[:, d_inner:2 * d_inner + 2 * N],
                  h[:, 2 * d_inner + 2 * N:])
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    conv = sum(xp[i:i + S] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    conv = c(jax.nn.silu(c(conv)))
    xs, B, C = (conv[:, :d_inner], conv[:, d_inner:d_inner + N],
                conv[:, d_inner + N:])
    dt = jax.nn.softplus(dt + p["dt_bias"])                      # (S, H)
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(S, H, Pd)
    y = _ssd(xh * dt[..., None], dt * A, B, C, min(s["chunk"], S))
    y = c(c(y + p["D"][:, None] * xh).reshape(S, d_inner) * jax.nn.silu(z))
    return c(x + prec.mm("se,ed->sd", c(_rms(y, p["norm"])), p["w_out"]))


def loss_sum(m, p, tokens, targets, mask, prec):
    """Sum over the rows of the masked next-token cross-entropy. ``prec``
    (``reference.Precision``) gives the matmuls and rounds each activation
    that the program keeps in its compute dtype; the scan is float32 in
    both. Each layer is recomputed in the backward pass."""
    layer = jax.checkpoint(lambda q, x: _block(m, q, x, prec))
    per_layer = {k[len("blocks.ssm."):] if k.startswith("blocks.ssm.")
                 else k[len("blocks."):]: v
                 for k, v in p.items() if k.startswith("blocks.")}
    total = jnp.zeros((), jnp.float32)
    for b in range(tokens.shape[0]):
        x = prec.cast(p["embed"][tokens[b]])
        for l in range(m["n_layers"]):
            x = layer({k: v[l] for k, v in per_layer.items()}, x)
        logits = prec.mm("sd,vd->sv", prec.cast(_rms(x, p["final_norm"])),
                         p["embed"], wide=True)
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, targets[b][:, None], -1)[:, 0]
        total = total + jnp.sum(ce * mask[b])
    return total
