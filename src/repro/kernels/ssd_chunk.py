"""Pallas TPU kernel: Mamba-2 SSD intra-chunk block.

The chunked SSD forward (models/ssm._ssd_chunked) is dominated by the
intra-chunk quadratic part: per chunk, per head,
    Y_intra = (tril(C·Bᵀ ∘ exp(segsum(a)))) · X
Those are (L×N)·(N×L) and (L×L)·(L×P) matmuls — MXU food — with an (L×L)
decay mask that should never leave VMEM. This kernel computes one chunk's
intra-chunk output per grid cell with the (L,L) tile resident in VMEM;
the (cheap, sequential) inter-chunk state pass stays in JAX.

Grid: (batch·heads, n_chunks). Layout: X (BH, S, P); B,C (BH, S, N)
pre-broadcast per head; a (BH, S) log-decay. Compiled, L must be a
multiple of 128 (TPU lane); N, P multiples of 128 preferred.

Oracle: kernels/ref.ssd_intra_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_intra_kernel(cr_ref, cc_ref, x_ref, b_ref, c_ref, y_ref, *, L: int):
    cum_row = cr_ref[0, 0]                           # (1, L)
    cum_col = cc_ref[0]                              # (L, 1)
    x = x_ref[0].astype(jnp.float32)                 # (L, P)
    b = b_ref[0].astype(jnp.float32)                 # (L, N)
    c = c_ref[0].astype(jnp.float32)                 # (L, N)

    g = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))   # (L, L)
    dec = cum_col - cum_row
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    m = jnp.where(ii >= jj, g * jnp.exp(dec), 0.0)
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())))   # (L, P)
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_intra_chunk(a, x, b, c, *, chunk: int, interpret: bool = False):
    """a: (BH, S) log-decay; x: (BH, S, P); b, c: (BH, S, N).
    Returns intra-chunk Y (BH, S, P) (inter-chunk term handled outside).

    The within-chunk cumulative decay is taken here, once, and handed to
    the kernel both as a row and as a column, so the (L, L) decay mask is a
    broadcast difference and every block keeps a lane-dense last dim."""
    BH, S = a.shape
    P = x.shape[-1]
    N = b.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    if not interpret and L % 128:
        raise ValueError(f"ssd_intra_chunk compiles for chunk lengths that "
                         f"are multiples of 128 lanes, got {L}")
    nc = S // L
    cum = jnp.cumsum(a.astype(jnp.float32).reshape(BH, nc, L), axis=-1)
    cum_row = cum.reshape(BH, nc, 1, L)
    cum_col = cum.reshape(BH, S, 1)
    kernel = functools.partial(_ssd_intra_kernel, L=L)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L), lambda h, i: (h, i, 0, 0)),
            pl.BlockSpec((1, L, 1), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, L, P), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, L, N), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, L, N), lambda h, i: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, L, P), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        interpret=interpret,
    )(cum_row, cum_col, x, b, c)
