"""Jitted public wrappers for the Pallas kernels.

Every kernel compiles for the TPU (Mosaic). ``interpret=True`` runs the
kernel body on the CPU instead — what the tests and CPU rehearsals pass;
nothing switches to it on its own.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import elastic_update as _eu
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_chunk as _ssd


# one f32 vreg: 8 sublanes x 128 lanes. A 1-D block that is a multiple of it
# tiles without a partial vreg, which Mosaic refuses.
_VREG = 8 * 128


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=False):
    """q: (B, S, H, D); k, v: (B, S, KVH, D). GQA is expanded head-wise
    before the kernel (K/V stay small in HBM; expansion happens once)."""
    B, S, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, v.shape[-1])
    out = _fa.flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)
    return out.reshape(B, H, S, -1).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("eta", "rho", "mu", "n_workers", "block",
                                   "interpret"))
def elastic_update(w, v, g, c, mean_w, *, eta, rho, mu, n_workers,
                   block=128 * 1024, interpret=False):
    """Flat buffers of any length n. ``block`` must be a multiple of one
    vreg (1024 elements); a length that is not a multiple of the block
    (capped at n rounded up to a vreg) is zero-padded and cut back, which
    costs one extra pass only for unaligned buffers (packed ones are
    aligned by ``core.packing``)."""
    if block % _VREG:
        raise ValueError(f"block {block} is not a multiple of {_VREG}")
    n = w.shape[0]
    block = min(block, -(-n // _VREG) * _VREG)
    pad = -n % block
    bufs = (w, v, g, c, mean_w)
    if pad:
        bufs = tuple(jnp.pad(x, (0, pad)) for x in bufs)
    out = _eu.fused_elastic_update(*bufs, eta=eta, rho=rho, mu=mu,
                                   n_workers=n_workers, block=block,
                                   interpret=interpret)
    return tuple(o[:n] for o in out) if pad else out


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_intra_chunk(a, x, b, c, *, chunk=256, interpret=False):
    return _ssd.ssd_intra_chunk(a, x, b, c, chunk=chunk, interpret=interpret)


@partial(jax.jit, static_argnames=("block_t", "block_v", "interpret"))
def fused_cross_entropy(h, w, targets, *, block_t=256, block_v=512,
                        interpret=False):
    from repro.kernels import fused_ce as _ce
    return _ce.fused_cross_entropy(h, w, targets, block_t=block_t,
                                   block_v=block_v, interpret=interpret)
