"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060), TPU-adapted.

The SSD layer computes, per head h with scalar decay a_t = exp(Δt·A_h):

    S_t = a_t · S_{t-1} + Δt·B_t ⊗ x_t          (state: head_dim × d_state)
    y_t = C_t · S_t + D_h · x_t

Training/prefill uses the CHUNKED form (the paper's matmul-friendly
decomposition, which is exactly what the MXU wants):
  * intra-chunk: quadratic attention-like matmuls within a chunk,
  * inter-chunk: a sequential scan over chunk states.
We scan over chunks (lax.scan) so the (L×L) decay tensor exists for one
chunk at a time — heads shard over `model`, batch over `data`, keeping the
per-device tile VMEM-sized. This mirrors the Pallas kernel's blocking
(kernels/ssd_chunk.py); this function is also its oracle.

Decode is the O(1) recurrent step on the carried (B, H, P, N) state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import sctx
from repro.models.common import ModelConfig, ParamDef


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.d_state
    return d_inner, n_heads, conv_dim


def ssm_defs(cfg: ModelConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    d_inner, n_heads, conv_dim = _dims(cfg)
    # in_proj emits [z (d_inner) | x (d_inner) | B (N) | C (N) | dt (H)]
    return {
        "w_in": ParamDef((d, 2 * d_inner + 2 * s.d_state + n_heads),
                         ("embed", "inner")),
        "conv_w": ParamDef((s.d_conv, conv_dim), ("conv", "inner")),
        "conv_b": ParamDef((conv_dim,), ("inner",), init="zeros"),
        "A_log": ParamDef((n_heads,), ("state",), init="zeros"),
        "D": ParamDef((n_heads,), ("state",), init="ones"),
        "dt_bias": ParamDef((n_heads,), ("state",), init="zeros"),
        "norm": ParamDef((d_inner,), ("inner",), init="zeros"),
        "w_out": ParamDef((d_inner, d), ("inner", "embed_out")),
    }


def _split_in(cfg, h):
    s = cfg.ssm
    d_inner, n_heads, _ = _dims(cfg)
    z = h[..., :d_inner]
    x = h[..., d_inner:2 * d_inner]
    B = h[..., 2 * d_inner:2 * d_inner + s.d_state]
    C = h[..., 2 * d_inner + s.d_state:2 * d_inner + 2 * s.d_state]
    dt = h[..., 2 * d_inner + 2 * s.d_state:]
    return z, x, B, C, dt


def _causal_conv(x, w, b):
    """Depthwise causal conv over time. x: (B,S,C); w: (K,C); b: (C,)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(K))
    return out + b[None, None]


@jax.named_scope("ssm.scan")
def _ssd_chunked(xh, dt, A, Bm, Cm, chunk: int, state0=None):
    """Chunked SSD scan.

    xh: (B,S,H,P) inputs (already Δt-scaled NOT applied; we apply here),
    dt: (B,S,H) softplus'ed step sizes, A: (H,) negative decay rates,
    Bm, Cm: (B,S,N) input/output projections (single group),
    Returns y: (B,S,H,P) and final state (B,H,P,N).
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        # dt=0 padding: decay=1 and zero input, so the state is unaffected
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // L

    a = dt * A[None, None, :]                       # (B,S,H) log-decay ≤ 0
    xbar = xh * dt[..., None]                       # Δt·x
    r = lambda t: t.reshape(Bsz, nc, L, *t.shape[2:])
    a_c, x_c, B_c, C_c = r(a), r(xbar), r(Bm), r(Cm)

    if state0 is None:
        state0 = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def chunk_step(state, inp):
        ac, xc, bc, cc = inp                        # (B,L,H), (B,L,H,P), (B,L,N)
        ac = ac.astype(jnp.float32)
        cum = jnp.cumsum(ac, axis=1)                # decay from chunk start
        total = cum[:, -1]                          # (B,H)

        # inter-chunk: y_prev[i] = exp(cum_i) · C_i · S_prev
        y_prev = jnp.einsum("bln,bhpn->blhp", cc.astype(jnp.float32), state)
        y_prev = y_prev * jnp.exp(cum)[..., None]

        # intra-chunk (the quadratic/matmul part)
        g = jnp.einsum("bln,bmn->blm", cc.astype(jnp.float32),
                       bc.astype(jnp.float32))      # (B,L,L)
        # mask before exp: above the diagonal the exponent is positive and
        # can pass float32's range; an inf masked after exp is 0 going
        # forward but 0 * inf = NaN in the backward pass
        mask = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
        dec = jnp.exp(jnp.where(mask, cum[:, :, None, :] - cum[:, None, :, :],
                                -jnp.inf))                  # (B,L,L,H)
        m = g[..., None] * dec
        y_intra = jnp.einsum("blmh,bmhp->blhp", m, xc.astype(jnp.float32))

        # state passing: S_new = exp(total)·S + Σ_j exp(total-cum_j) B_j x_jᵀ
        decay_in = jnp.exp(total[:, None, :] - cum)  # (B,L,H)
        s_in = jnp.einsum("bln,blh,blhp->bhpn", bc.astype(jnp.float32),
                          decay_in, xc.astype(jnp.float32))
        state_new = state * jnp.exp(total)[:, :, None, None] + s_in
        return state_new, y_prev + y_intra

    state, y = lax.scan(chunk_step, state0,
                        (a_c.swapaxes(0, 1), x_c.swapaxes(0, 1),
                         B_c.swapaxes(0, 1), C_c.swapaxes(0, 1)))
    y = y.swapaxes(0, 1).reshape(Bsz, S, H, P)
    if pad:
        y = y[:, :S - pad]
    return y, state


def ssd_step(state, x_t, dt_t, A, B_t, C_t):
    """O(1) decode recurrence. state: (B,H,P,N); x_t: (B,H,P);
    dt_t: (B,H); B_t, C_t: (B,N)."""
    a = jnp.exp(dt_t * A[None, :])[..., None, None]          # (B,H,1,1)
    upd = jnp.einsum("bn,bhp->bhpn", B_t.astype(jnp.float32),
                     (x_t * dt_t[..., None]).astype(jnp.float32))
    state = state * a + upd
    y = jnp.einsum("bn,bhpn->bhp", C_t.astype(jnp.float32), state)
    return state, y


@jax.named_scope("ssm")
def ssm_block(cfg: ModelConfig, p, x, positions=None, *, cache=None,
              cache_pos=None, **_unused):
    """Mamba-2 block. cache = {conv: (B,K-1,convdim), state: (B,H,P,N)}."""
    s = cfg.ssm
    cd = cfg.compute_dtype
    d_inner, n_heads, conv_dim = _dims(cfg)
    B_, S, _ = x.shape

    h = jnp.einsum("bsd,de->bse", x, p["w_in"].astype(cd))
    z, xi, Bm, Cm, dt = _split_in(cfg, h)
    z = sctx.shard(z, "batch", "seq", "inner")
    xbc = sctx.shard(jnp.concatenate([xi, Bm, Cm], axis=-1),
                     "batch", "seq", "inner")

    if cache is not None and S == 1:
        # decode: sliding conv state + recurrent SSD step
        conv_hist = jnp.concatenate([cache["conv"], xbc], axis=1)  # (B,K,cd)
        conv_out = jnp.einsum("bkc,kc->bc", conv_hist.astype(cd),
                              p["conv_w"].astype(cd)) + p["conv_b"].astype(cd)
        conv_out = jax.nn.silu(conv_out)[:, None]                  # (B,1,cd)
        xi, Bm, Cm = (conv_out[..., :d_inner],
                      conv_out[..., d_inner:d_inner + s.d_state],
                      conv_out[..., d_inner + s.d_state:])
        dt_t = jax.nn.softplus(dt[:, 0] + p["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        xh = xi.reshape(B_, n_heads, s.head_dim)
        state, y = ssd_step(cache["state"], xh, dt_t, A, Bm[:, 0], Cm[:, 0])
        y = y + p["D"].astype(jnp.float32)[None, :, None] * xh
        y = y.reshape(B_, 1, d_inner)
        new_cache = {"conv": conv_hist[:, 1:], "state": state}
    else:
        conv_out = jax.nn.silu(_causal_conv(xbc.astype(cd),
                                            p["conv_w"].astype(cd),
                                            p["conv_b"].astype(cd)))
        xi = conv_out[..., :d_inner]
        Bm = conv_out[..., d_inner:d_inner + s.d_state]
        Cm = conv_out[..., d_inner + s.d_state:]
        dt_sp = jax.nn.softplus(dt.astype(jnp.float32)
                                + p["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        xh = sctx.shard(xi.reshape(B_, S, n_heads, s.head_dim),
                        "batch", "seq", "heads", "head_dim")
        y, state = _ssd_chunked(xh.astype(jnp.float32), dt_sp, A, Bm, Cm,
                                s.chunk)
        y = y + p["D"].astype(jnp.float32)[None, None, :, None] * xh
        y = y.reshape(B_, S, d_inner)
        new_cache = cache
        if cache is not None:
            K = s.d_conv
            new_cache = {"conv": xbc[:, -(K - 1):].astype(cache["conv"].dtype),
                         "state": state}

    # gated RMSNorm (Mamba-2) + out proj
    y = sctx.shard(y.astype(cd), "batch", "seq", "inner") * jax.nn.silu(z)
    y32 = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(y32), axis=-1, keepdims=True)
    y = (y32 * lax.rsqrt(var + 1e-6)
         * (1.0 + p["norm"].astype(jnp.float32))).astype(cd)
    return jnp.einsum("bse,ed->bsd", y, p["w_out"].astype(cd)), new_cache
