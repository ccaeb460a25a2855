"""Pallas TPU kernel: fused ("flash") cross-entropy.

§Perf cell A identified the LM loss as irreducible in XLA: the (T, V)
logits round-trip HBM (≈1 PB/step global for gemma-3's 262k vocab at 1M
tokens). This kernel tiles the vocab dim and keeps each (block_t × block_v)
logits tile in VMEM, maintaining an online logsumexp and the target-logit
gather — HBM traffic drops from O(T·V) to O(T·d + V·d):

    loss_t = logsumexp_v(h_t·W_v) − (h_t·W_{y_t})

Grid: (token_blocks, vocab_blocks), vocab innermost/sequential with
running (m, l, tgt) VMEM scratch. Forward-only (the training path's
backward still uses the chunked XLA loss; wiring a custom VJP through this
kernel is the documented next step). Oracle: kernels/ref.fused_ce_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ce_kernel(h_ref, w_ref, t_ref, loss_ref, m_ref, l_ref, tgt_ref, *,
               block_v: int, n_v: int, vocab: int):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        tgt_ref[...] = jnp.zeros_like(tgt_ref)

    h = h_ref[...]                                  # (bt, d)
    w = w_ref[...]                                  # (d, bv)
    logits = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (bt, bv)
    v_ids = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    logits = jnp.where(v_ids < vocab, logits, NEG_INF)

    m_prev = m_ref[...]                             # (bt, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.exp(logits - m_new).sum(
        axis=1, keepdims=True)
    m_ref[...] = m_new

    # target logit if it falls inside this vocab tile
    hit = v_ids == t_ref[...]                       # (bt, bv) vs (bt, 1)
    tgt_ref[...] = tgt_ref[...] + jnp.where(hit, logits, 0.0).sum(
        axis=1, keepdims=True)

    @pl.when(vj == n_v - 1)
    def _finalize():
        lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
        loss_ref[...] = (lse - tgt_ref[...]).astype(loss_ref.dtype)


def fused_cross_entropy(h, w, targets, *, block_t: int = 256,
                        block_v: int = 512, interpret: bool = False):
    """h: (T, d); w: (d, V); targets: (T,) int32 -> per-token loss (T,).

    Targets, loss and the running statistics travel as (T, 1) columns: a
    1-D int32 block must match XLA's 1024-element tiling, which a token
    block of 256 does not; a column block needs only bt % 8 == 0.
    ``block_v`` 2048 runs out of VMEM on a v5e at d = 3072 (the
    double-buffered (d, bv) weight block); 512 and 1024 compile."""
    T, d = h.shape
    V = w.shape[1]
    bt = min(block_t, T)
    bv = min(block_v, V)
    pt, pv = (-T) % bt, (-V) % bv
    if pt:
        h = jnp.pad(h, ((0, pt), (0, 0)))
        targets = jnp.pad(targets, (0, pt))
    if pv:
        w = jnp.pad(w, ((0, 0), (0, pv)))
    n_t, n_v = (T + pt) // bt, (V + pv) // bv

    kernel = functools.partial(_ce_kernel, block_v=bv, n_v=n_v, vocab=V)
    loss = pl.pallas_call(
        kernel,
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T + pt, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(h, w, targets[:, None])
    return loss[:T, 0]
