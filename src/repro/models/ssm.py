"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060), TPU-adapted.

The SSD layer computes, per head h with scalar decay a_t = exp(Δt·A_h):

    S_t = a_t · S_{t-1} + Δt·B_t ⊗ x_t          (state: head_dim × d_state)
    y_t = C_t · S_t + D_h · x_t

Training/prefill uses the CHUNKED form (the paper's matmul-friendly
decomposition, which is exactly what the MXU wants):
  * intra-chunk: quadratic attention-like matmuls within a chunk,
  * inter-chunk: a sequential scan over chunk states.
``_ssd_chunked`` scans over chunks (lax.scan) so the (L×L) decay tensor
exists for one chunk at a time; it is the oracle of the TPU path. Lowered for
a TPU, ``ssd_scan`` computes every chunk's contractions over its positions
(the intra-chunk term, the state it adds, the term from the state entering
it) at once in Pallas kernels (kernels/ssd_chunk.py) whose (L×L) tiles stay
in VMEM, and the lax.scan keeps only the elementwise state recurrence.

Decode is the O(1) recurrent step on the carried (B, H, P, N) state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from repro.kernels import ssd_chunk
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


def _kernel_placement(xh, Bm, chunk):
    """Where the chunk kernels run these shapes: (mesh, each operand kind's
    spec, as ``ssd_chunk`` names the kinds) on the installed mesh, (None,
    None) with none installed, or None where the kernels refuse them. They
    take one group of B and C, a chunk that is a multiple of 128 lanes,
    whole sequences per device, and per device a number of heads that
    ``ssd_chunk.head_block`` can tile."""
    Bsz, S, H, P = xh.shape
    if Bm.ndim != 3 or min(chunk, S) % ssd_chunk.LANES:
        return None
    lay = sctx.layout()
    if lay is None:
        return (None, None) if ssd_chunk.head_block(H, P) else None
    batch, seq, heads, _ = lay.spec(xh.shape,
                                    ("batch", "seq", "heads", "head_dim"))
    n_heads = lay.mesh.shape[heads] if heads else 1
    if seq is not None or not ssd_chunk.head_block(H // n_heads, P):
        return None
    return lay.mesh, {"x": PartitionSpec(batch, None, heads),
                      "rows": PartitionSpec(batch, heads, None),
                      "bc": PartitionSpec(batch, None, None),
                      "s": PartitionSpec(None, batch, heads, None)}


def _per_device(placement, kernel, ins, out, *args):
    """``kernel(*args)`` under the scope that records the chunk kernels, per
    device under ``jax.shard_map`` over the installed mesh (batch over data,
    heads over model; its transpose sums dB and dC over the head shards);
    ``ins`` and ``out`` name the operands' specs."""
    mesh, specs = placement
    with jax.named_scope("ssm.scan.kernel"):
        if mesh is None:
            return kernel(*args)
        return jax.shard_map(kernel, mesh=mesh,
                             in_specs=tuple(specs[k] for k in ins),
                             out_specs=specs[out], check_vma=False)(*args)


@jax.named_scope("ssm.scan")
def _ssd_chunked_kernel(xh, dt, A, Bm, Cm, chunk: int, state0=None, *,
                        placement, interpret=False):
    """``_ssd_chunked`` with its contractions over each chunk's positions in
    the Pallas kernels of ``kernels/ssd_chunk``: the state each chunk adds
    (``chunk_states``) and its output (``chunk_output``), every chunk in one
    call each. Between them a lax.scan passes the state from chunk to chunk,
    S ← exp(cum_{L−1})·S + S_in, the same recurrence as ``_ssd_chunked``'s.
    B and C go in as float32, as the jnp path multiplies them, so that
    their gradients are summed in float32 and rounded once."""
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

    a = (dt * A[None, None, :]).astype(jnp.float32)  # (B,S,H) log-decay ≤ 0
    cum = jnp.cumsum(a.reshape(Bsz, nc, L, H), axis=2)   # from chunk start
    rows = lambda t: t.reshape(Bsz, S, H).transpose(0, 2, 1)   # (B,H,S)
    # x and dt go in apart: the kernels form Δt·x in VMEM
    args = (rows(cum), rows(dt.astype(jnp.float32)),
            xh.reshape(Bsz, S, H * P), Bm.astype(jnp.float32))
    s_in = _per_device(placement, partial(ssd_chunk.chunk_states, chunk=L,
                                          interpret=interpret),
                       ("rows", "rows", "x", "bc"), "s", *args)

    # state passing: S_new = exp(total)·S + S_in, states (B, H·P, N)
    decay = jnp.repeat(jnp.exp(cum[:, :, -1]), P, axis=-1)  # (B,nc,H·P)
    if state0 is None:
        state0 = jnp.zeros((Bsz, H, P, N), jnp.float32)

    def chunk_step(state, inp):
        d, s = inp
        return state * d[..., None] + s, state

    state, s_prev = lax.scan(chunk_step, state0.reshape(Bsz, H * P, N),
                             (decay.swapaxes(0, 1), s_in))
    y = _per_device(placement, partial(ssd_chunk.chunk_output, chunk=L,
                                       interpret=interpret),
                    ("rows", "rows", "x", "bc", "bc", "s"), "x", *args,
                    Cm.astype(jnp.float32), s_prev)
    y = y.reshape(Bsz, S, H, P)
    if pad:
        y = y[:, :S - pad]
    return y, state.reshape(Bsz, H, P, N)


def ssd_scan(xh, dt, A, Bm, Cm, chunk: int, state0=None):
    """The chunked SSD scan. Lowered for a TPU, the shapes the chunk
    kernels take (``_kernel_placement``) run ``_ssd_chunked_kernel``; every
    other shape, and every other platform, runs ``_ssd_chunked``."""
    placement = _kernel_placement(xh, Bm, chunk)
    if placement is None:
        return _ssd_chunked(xh, dt, A, Bm, Cm, chunk, state0)

    def kernel(xh, dt, A, Bm, Cm, state0):
        return _ssd_chunked_kernel(xh, dt, A, Bm, Cm, chunk, state0,
                                   placement=placement)

    def jnp_path(xh, dt, A, Bm, Cm, state0):
        return _ssd_chunked(xh, dt, A, Bm, Cm, chunk, state0)

    return lax.platform_dependent(xh, dt, A, Bm, Cm, state0, tpu=kernel,
                                  default=jnp_path)


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
        y, state = ssd_scan(xh.astype(jnp.float32), dt_sp, A, Bm, Cm,
                            s.chunk)
        # the skip in (B, S, d_inner): with head_dim minor, XLA lays x-sized
        # arrays out sequence-minor and copies them to and from the kernels
        y = y.reshape(B_, S, d_inner) + jnp.repeat(
            p["D"].astype(jnp.float32), s.head_dim) * xi
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
