"""Pallas TPU kernel: fused packed EASGD update (the paper's hot spot).

Paper Table 3: the weight update is 16–23% of step time — it is a pure
HBM-bandwidth elementwise pass. Done naively (eqs 5–6 then eq 2 as separate
jnp ops) the buffers round-trip HBM several times; fused, each of the five
input buffers is read ONCE and the three outputs written ONCE — the
bandwidth floor:

    V' = μ·V − η·G
    W' = W + V' − η·ρ·(W − C)
    C' = C + η·ρ·P·(M − C)          (M = cross-pod mean of W, pre-update)

All buffers are the packer's flat 1-D layout (contiguous — the §5.2
'single-layer layout'), tiled in (8·128·BLOCK)-element VMEM blocks.
Oracle: core.easgd.fused_elastic_step_flat.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.packing import ELASTIC_UPDATE_BLOCK


def _update_kernel(w_ref, v_ref, g_ref, c_ref, m_ref, w_out, v_out, c_out, *,
                   eta: float, rho: float, mu: float, n_workers: int):
    w = w_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v_new = mu * v - eta * g
    w_new = w + v_new - eta * rho * (w - c)
    c_new = c + eta * rho * n_workers * (m - c)
    w_out[...] = w_new.astype(w_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)
    c_out[...] = c_new.astype(c_out.dtype)


def fused_elastic_update(w, v, g, c, mean_w, *, eta: float, rho: float,
                         mu: float, n_workers: int,
                         block: int = ELASTIC_UPDATE_BLOCK,
                         interpret: bool = False):
    """All inputs 1-D, same length (packer-aligned). Returns (w', v', c').
    Compiles for the TPU; pass ``interpret=True`` to run it on the CPU."""
    n = w.shape[0]
    bs = min(block, n)
    assert n % bs == 0, (n, bs, "pack with align=block")
    grid = (n // bs,)
    spec = pl.BlockSpec((bs,), lambda i: (i,))
    kernel = functools.partial(_update_kernel, eta=eta, rho=rho, mu=mu,
                               n_workers=n_workers)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * 5,
        out_specs=[spec] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((n,), w.dtype),
            jax.ShapeDtypeStruct((n,), v.dtype),
            jax.ShapeDtypeStruct((n,), c.dtype),
        ],
        interpret=interpret,
    )(w, v, g, c, mean_w)


# ---------------------------------------------------------------------------
# f64 per-bucket sync-family updates — the p2p data plane's hot path
# ---------------------------------------------------------------------------
#
# These two kernels are BITWISE replacements for the easgd_flat update pair
# the p2p worker runs on each completed bucket (``net.worker._p2p_sync_loop``
# with ``update_backend="pallas"``): same f64 dtype, same operation ASTs as
# the numpy expressions, so IEEE-754 guarantees equal bits — PROVIDED the
# XLA CPU backend does not contract a·b+c into fused multiply-adds (an fma
# keeps the product's infinite precision through the add; numpy rounds
# twice). The worker/launcher therefore pin ``XLA_FLAGS=--xla_cpu_max_isa=
# SSE4_2`` before the first jax import — SSE4.2 predates the FMA ISA
# extension, so LLVM cannot emit fma and the kernels match numpy bit for
# bit (pinned at zero tolerance by tests/test_bucketing.py). Without the
# flag the results are still correct to ~1 ulp, just not identical.
#
# They are interpret-only by design: the TPU has no f64, and they serve the
# CPU-pinned host plane. Their callers (the p2p worker, the tests) pass
# ``interpret=True``; without it Pallas refuses to lower them on the CPU.

def _sync_easgd_kernel(w_ref, g_ref, c_ref, r_ref, w_out, c_out, *,
                       eta: float, rho: float, alpha_p: float, p: int):
    w = w_ref[...]
    g = g_ref[...]
    c = c_ref[...]
    r = r_ref[...]
    # exact easgd_flat op order: worker_step's elastic rule on the PRE-
    # update center, then eq 2 on the exchanged pre-update weight sum r
    w_out[...] = w - eta * (g + rho * (w - c))
    c_out[...] = c + alpha_p * (r / p - c)


def _sync_sgd_kernel(c_ref, v_ref, r_ref, c_out, v_out, *,
                     eta: float, mu: float, p: int):
    c = c_ref[...]
    v = v_ref[...]
    r = r_ref[...]
    v_new = mu * v - eta * (r / p)
    c_out[...] = c + v_new
    v_out[...] = v_new


def _bucket_grid(n: int, block: int):
    """(block_size, grid): buckets cut at layer edges are rarely an exact
    multiple of the VMEM block, so an unaligned bucket runs as one block —
    functionally identical, just untiled."""
    bs = min(block, n)
    if n % bs:
        bs = n
    return bs, (n // bs,)


def fused_sync_easgd_update(w, grad, center, row, p: int,
                            eta: float, rho: float, *,
                            block: int = ELASTIC_UPDATE_BLOCK,
                            interpret: bool = False):
    """One bucket's fused Sync EASGD update (worker rule + center pull in
    a single pass over the slices — five reads, two writes):

        W' = W − η(G + ρ(W − C))
        C' = C + ηρP(R/P − C)        (R = exchanged Σ_i W_i, pre-update)

    Returns ``(w', c')`` as f64 numpy arrays; the caller assigns them back
    into its bucket slices. Interpret-only (f64, host plane): pass
    ``interpret=True``."""
    n = w.shape[0]
    bs, grid = _bucket_grid(n, block)
    spec = pl.BlockSpec((bs,), lambda i: (i,))
    kernel = functools.partial(_sync_easgd_kernel, eta=eta, rho=rho,
                               alpha_p=(eta * rho) * p, p=p)
    with jax.enable_x64(True):
        w_new, c_new = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[spec] * 4,
            out_specs=[spec] * 2,
            out_shape=[jax.ShapeDtypeStruct((n,), jnp.float64)] * 2,
            interpret=interpret,
        )(w, grad, center, row)
        return np.asarray(w_new), np.asarray(c_new)


def fused_sync_sgd_update(center, vel, row, p: int,
                          eta: float, mu: float, *,
                          block: int = ELASTIC_UPDATE_BLOCK,
                          interpret: bool = False):
    """One bucket's fused synchronous momentum-SGD master update:

        V̄' = μV̄ − η(R/P);  C' = C + V̄'     (R = exchanged Σ_i grad_i)

    Returns ``(c', v̄')`` as f64 numpy arrays. Interpret-only (f64, host
    plane): pass ``interpret=True``."""
    n = center.shape[0]
    bs, grid = _bucket_grid(n, block)
    spec = pl.BlockSpec((bs,), lambda i: (i,))
    kernel = functools.partial(_sync_sgd_kernel, eta=eta, mu=mu, p=p)
    with jax.enable_x64(True):
        c_new, v_new = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[spec] * 3,
            out_specs=[spec] * 2,
            out_shape=[jax.ShapeDtypeStruct((n,), jnp.float64)] * 2,
            interpret=interpret,
        )(center, vel, row)
        return np.asarray(c_new), np.asarray(v_new)
