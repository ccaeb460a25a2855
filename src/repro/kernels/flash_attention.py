"""Pallas TPU flash attention: online-softmax with explicit VMEM tiling.

TARGET: TPU v5e MXU. Tiles are (block_q × head_dim) and (block_k × head_dim)
in VMEM (128-multiples → MXU-aligned); the (block_q × block_k) score tile
never leaves VMEM — HBM traffic is O(S·D) instead of O(S²).

Grid: (batch·heads, n_q_blocks, n_k_blocks) with the innermost dim
sequential — running max/denominator/accumulator live in VMEM scratch
across the k-block sweep (the standard TPU flash pattern; same math as the
pure-JAX oracle models/attention.blocked_attention).

Validated on CPU with interpret=True; it compiles for the TPU otherwise.
Causal + sliding-window masks are supported; GQA is
handled in the wrapper by expanding K/V head-wise (ops.flash_attention).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int, block_q: int,
                 block_k: int, n_k: int, seq_len: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0].astype(jnp.float32)                  # (bk, Dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < seq_len
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=0, block_q=128,
                         block_k=128, interpret=False):
    """q, k, v: (BH, S, D) with matched heads (GQA expanded by the wrapper).
    Returns (BH, S, Dv)."""
    BH, S, D = q.shape
    Dv = v.shape[-1]
    bq = min(block_q, S)
    bk = min(block_k, S)
    pad_q = (-S) % bq
    pad_k = (-S) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    nq = (S + pad_q) // bq
    nk = (S + pad_k) // bk

    kernel = functools.partial(
        _attn_kernel, scale=1.0 / math.sqrt(D), causal=causal, window=window,
        block_q=bq, block_k=bk, n_k=nk, seq_len=S)

    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S + pad_q, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :S]
