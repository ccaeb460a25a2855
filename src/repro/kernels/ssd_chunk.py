"""Pallas TPU kernels: the Mamba-2 SSD scan's per-chunk terms, forward and
backward.

The chunked SSD scan (models/ssm) splits a sequence into chunks of L
positions. For every head that shares the one group's B and C, with cum the
head's cumulative log-decay from the chunk's start and S the state entering
the chunk, the chunk's output is

    y[l] = Σ_{m≤l} (C_l·B_m) · exp(cum_l − cum_m) · x̄_m      (intra-chunk)
         + exp(cum_l) · S·C_l                                (from S)

and the chunk adds to the state it passes on

    S_in = Σ_l exp(cum_{L−1} − cum_l) · x̄_l ⊗ B_l.

Both are contractions over the chunk's positions: MXU work whose (L, L)
products and decay tiles never need to leave VMEM. ``chunk_states`` computes
S_in and ``chunk_output`` computes y, each as one kernel call over a grid of
(rows, chunks, head blocks), each with a custom VJP whose backward is a
second kernel that recomputes g = C·Bᵀ, the decay tiles and the weights, as
flash attention's backward does: the only residuals are the kernels'
inputs. The sequential state pass between them, S ← exp(cum_{L−1})·S + S_in,
is elementwise and stays in JAX.

The kernels take x and Δt apart and form x̄ = Δt·x in VMEM, so that XLA
never multiplies the (R, S, H) step sizes into an x-sized array: it lays
such a product out with positions minor (48 heads would pad to 128 lanes)
and then copies x and y between that layout and the model's. Layout: x and
y (R, S, H·P), heads major in the last dim, as the model holds them; cum
and Δt (R, H, S), each head's values a row; B and C (R, S, N), indexed per
row by the BlockSpec and never broadcast per head; states (chunks, R, H·P,
N). Inside, a block of x is transposed once, so that a head is P sublanes
of x̄ᵀ (hb·P, L). The exponent is masked to −inf before ``exp``: above the
diagonal it is positive and can pass float32's range, and 0·inf is NaN in
the backward pass.

Products take bfloat16 operands and accumulate in float32: the one pass that
XLA's default precision gives a float32 einsum on a TPU. Like those
einsums, they multiply in float32 instead under
``jax.default_matmul_precision("highest")`` (or "float32"). Compiled, L must
be a multiple of 128 lanes and P of 8 sublanes (``head_block``).

Oracles: kernels/ref.ssd_intra_ref; the jnp path of models/ssm._ssd_chunked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ bᵀ
_TN = (((0,), (0,)), ((), ()))      # aᵀ @ b


def head_block(heads: int, head_dim: int) -> int | None:
    """Heads per grid cell: 8 (a sublane tile of cum's rows) where their
    lanes of x are whole lane tiles, else all of them; None where a head is
    not whole sublane tiles of x̄ᵀ or the block's lanes are not whole tiles
    of x."""
    if head_dim % SUBLANES:
        return None
    if heads % SUBLANES == 0 and SUBLANES * head_dim % LANES == 0:
        return SUBLANES
    return heads if heads * head_dim % LANES == 0 else None


def _dot(a, b, dims, precision):
    if precision == lax.Precision.HIGHEST:
        return lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               dims, precision=precision,
                               preferred_element_type=jnp.float32)
    return lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                           dims, preferred_element_type=jnp.float32)


def _by_head(v, P):
    """(hb, L) → (hb·P, L): each head's row over its P sublanes."""
    return jnp.concatenate([jnp.broadcast_to(v[h:h + 1], (P, v.shape[1]))
                            for h in range(v.shape[0])], axis=0)


def _head_sums(v, hb, P):
    """(hb·P, L) → (hb, L): the sum over each head's P sublanes."""
    return jnp.concatenate([jnp.sum(v[h * P:(h + 1) * P], axis=0,
                                    keepdims=True) for h in range(hb)],
                           axis=0)


def _accumulate(acc_ref, value):
    """Write at the first head block of a (row, chunk), add at the rest:
    dB and dC sum over every head."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[0] = value

    @pl.when(pl.program_id(2) > 0)
    def _():
        acc_ref[0] += value


def _xbar_t(x_ref, dt_ref, P):
    """(x̄ᵀ, xᵀ, Δt over each head's sublanes), each (hb·P, L) float32."""
    xt = x_ref[0].astype(jnp.float32).T
    dtb = _by_head(dt_ref[0], P)
    return dtb * xt, xt, dtb


def _store_x(ref, dxbt, xt, dtb, P):
    """d(x̄ᵀ) → dx, stored as x is laid out; returns d(Δt), (hb, L)."""
    ref[0] = (dxbt * dtb).T
    return _head_sums(dxbt * xt, dtb.shape[0] // P, P)


def _to_end(rows, L):
    """exp(cum_{L−1} − cum_l), (hb, L): each position's decay to the
    chunk's end (the exponent is ≤ 0)."""
    return jnp.exp(rows[:, L - 1:L] - rows)


# ---------------------------------------------------------------------------
# chunk_states: S_in, the state a chunk adds
# ---------------------------------------------------------------------------

def _states_fwd_kernel(cum_ref, dt_ref, x_ref, b_ref, s_ref, *, P, L,
                       precision):
    w = _by_head(_to_end(cum_ref[0], L), P)             # (hb·P, L)
    xbt = _xbar_t(x_ref, dt_ref, P)[0]
    s_ref[0, 0] = _dot(w * xbt, b_ref[0], _NN, precision)


def _states_bwd_kernel(cum_ref, dt_ref, x_ref, b_ref, ds_ref, dx_ref,
                       ddt_ref, dcum_ref, db_ref, *, P, L, precision):
    rows = cum_ref[0]
    hb = rows.shape[0]
    w = _by_head(_to_end(rows, L), P)
    xbt, xt, dtb = _xbar_t(x_ref, dt_ref, P)
    ds = ds_ref[0, 0]
    dwx = _dot(ds, b_ref[0], _NT, precision)            # (hb·P, L)
    ddt_ref[0] = _store_x(dx_ref, w * dwx, xt, dtb, P)
    _accumulate(db_ref, _dot(w * xbt, ds, _TN, precision))
    # d exp(cum_{L−1} − cum_l): −q at l, +Σ_l q at the chunk's end
    q = _head_sums(w * xbt * dwx, hb, P)                 # (hb, L)
    last = lax.broadcasted_iota(jnp.int32, q.shape, 1) == L - 1
    dcum_ref[0] = jnp.where(last, jnp.sum(q, axis=1, keepdims=True), 0.0) - q


# ---------------------------------------------------------------------------
# chunk_output: y, the intra-chunk term and the term from the entering state
# ---------------------------------------------------------------------------

def _causal(L, transposed):
    """[l, m] with l ≥ m; transposed, [m, l] with m ≤ l."""
    i = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    j = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    return j >= i if transposed else i >= j


def _output_fwd_kernel(cum_ref, dt_ref, x_ref, b_ref, c_ref, s_ref, y_ref,
                       *, P, L, precision):
    """yᵀ per head: x̄ᵀ·mᵀ with mᵀ[m, l] = B_m·C_l exp(cum_l − cum_m)."""
    cm = c_ref[0]
    gt = _dot(b_ref[0], cm, _NT, precision)             # (L, L): B_m·C_l
    rows = cum_ref[0]
    cols = rows.T                                       # (L, hb)
    causal_t = _causal(L, transposed=True)
    z = _dot(s_ref[0, 0], cm, _NT, precision)           # (hb·P, L): S·C_l
    y = _by_head(jnp.exp(rows), P) * z
    xbt = _xbar_t(x_ref, dt_ref, P)[0]
    out = []
    for h in range(rows.shape[0]):
        dec_t = jnp.exp(jnp.where(causal_t, rows[h:h + 1] - cols[:, h:h + 1],
                                  -jnp.inf))
        out.append(y[h * P:(h + 1) * P] + _dot(
            xbt[h * P:(h + 1) * P], gt * dec_t, _NN, precision))
    y_ref[0] = jnp.concatenate(out, axis=0).T


def _output_bwd_kernel(cum_ref, dt_ref, x_ref, b_ref, c_ref, s_ref, dy_ref,
                       dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, ds_ref, *,
                       P, L, precision):
    """Per head, with m[l, m] = g ∘ decay: dx̄ᵀ = dyᵀ·m, dm = dy·x̄ᵀ,
    dg = Σ_h dm ∘ decay, and d(cum) from dm ∘ m; the state's term with
    dz = exp(cum) ∘ dyᵀ: dS = dz·C, dC = dzᵀ·S, d(cum) = Σ_p dz ∘ (S·Cᵀ)."""
    bm, cm = b_ref[0], c_ref[0]
    g = _dot(cm, bm, _NT, precision)                    # (L, L): C_l·B_m
    rows = cum_ref[0]
    hb = rows.shape[0]
    cols = rows.T                                       # (L, hb)
    causal = _causal(L, transposed=False)
    dyt, st = dy_ref[0].T, s_ref[0, 0]
    xbt, xt, dtb = _xbar_t(x_ref, dt_ref, P)
    dz = _by_head(jnp.exp(rows), P) * dyt               # (hb·P, L)
    ds_ref[0, 0] = _dot(dz, cm, _NN, precision)
    dc = _dot(dz, st, _TN, precision)
    d_row = _head_sums(dz * _dot(st, cm, _NT, precision), hb, P)
    head = lax.broadcasted_iota(jnp.int32, (L, hb), 1)
    d_col = jnp.zeros((L, hb), jnp.float32)
    dg = jnp.zeros((L, L), jnp.float32)
    dxbt = []
    for h in range(hb):
        dec = jnp.exp(jnp.where(causal, cols[:, h:h + 1] - rows[h:h + 1],
                                -jnp.inf))
        m = g * dec
        dyh = dyt[h * P:(h + 1) * P]
        dxbt.append(_dot(dyh, m, _NN, precision))
        dm = _dot(dyh, xbt[h * P:(h + 1) * P], _TN, precision)  # dy_l·x̄_m
        dg = dg + dm * dec
        p = dm * m
        # exp(cum_l − cum_m): + at l, − at m
        d_col = d_col + jnp.where(head == h,
                                  jnp.sum(p, axis=1, keepdims=True), 0.0)
        d_row = d_row - jnp.where(
            lax.broadcasted_iota(jnp.int32, d_row.shape, 0) == h,
            jnp.sum(p, axis=0, keepdims=True), 0.0)
    ddt_ref[0] = _store_x(dx_ref, jnp.concatenate(dxbt, axis=0), xt, dtb, P)
    dcum_ref[0] = d_row + d_col.T
    _accumulate(db_ref, _dot(dg, cm, _TN, precision))   # Σ_l dg[l, m] C_l
    _accumulate(dc_ref, dc + _dot(dg, bm, _NN, precision))


# ---------------------------------------------------------------------------
# calls and VJPs
# ---------------------------------------------------------------------------

def _call(kernel, x, b, chunk, precision, interpret, ins, outs, args):
    """One pallas_call of ``kernel`` over the (rows, chunks, head blocks)
    grid. ``ins`` and ``outs`` give each operand's kind: "rows" (R, H, S),
    "x" (R, S, H·P), "bc" (R, S, N) or "s" (S/L, R, H·P, N); every output
    is float32."""
    R, H, S = next(a for k, a in zip(ins, args) if k == "rows").shape
    P = x.shape[-1] // H
    N = b.shape[-1]
    L = min(chunk, S)
    assert S % L == 0 and x.shape == (R, S, H * P), (x.shape, H, L)
    hb = head_block(H, P)
    if not interpret and (L % LANES or hb is None):
        raise ValueError(f"ssd_chunk compiles for chunk lengths that are "
                         f"multiples of 128 lanes and heads of whole sublane "
                         f"tiles in whole lane tiles, got L={L}, {H} heads "
                         f"of {P}")
    hb = hb or H
    spec = {"rows": pl.BlockSpec((1, hb, L), lambda r, c, h: (r, h, c)),
            "x": pl.BlockSpec((1, L, hb * P), lambda r, c, h: (r, c, h)),
            "bc": pl.BlockSpec((1, L, N), lambda r, c, h: (r, c, 0)),
            "s": pl.BlockSpec((1, 1, hb * P, N),
                              lambda r, c, h: (c, r, h, 0))}
    shape = {"rows": (R, H, S), "x": x.shape, "bc": b.shape,
             "s": (S // L, R, H * P, N)}
    return pl.pallas_call(
        functools.partial(kernel, P=P, L=L, precision=precision),
        grid=(R, S // L, H // hb),
        in_specs=[spec[k] for k in ins],
        out_specs=[spec[k] for k in outs],
        out_shape=[jax.ShapeDtypeStruct(shape[k], jnp.float32) for k in outs],
        compiler_params=pltpu.CompilerParams(
            # dB and dC sum over the head blocks of a (row, chunk)
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _states(cum, dt, x, b, chunk, precision, interpret):
    return _call(_states_fwd_kernel, x, b, chunk, precision, interpret,
                 ("rows", "rows", "x", "bc"), ("s",), (cum, dt, x, b))[0]


def _states_fwd(cum, dt, x, b, chunk, precision, interpret):
    return (_states(cum, dt, x, b, chunk, precision, interpret),
            (cum, dt, x, b))


def _states_bwd(chunk, precision, interpret, res, ds):
    cum, dt, x, b = res
    dx, ddt, dcum, db = _call(
        _states_bwd_kernel, x, b, chunk, precision, interpret,
        ("rows", "rows", "x", "bc", "s"), ("x", "rows", "rows", "bc"),
        (cum, dt, x, b, ds))
    return dcum, ddt, dx.astype(x.dtype), db


_states.defvjp(_states_fwd, _states_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _output(cum, dt, x, b, c, s, chunk, precision, interpret):
    return _call(_output_fwd_kernel, x, b, chunk, precision, interpret,
                 ("rows", "rows", "x", "bc", "bc", "s"), ("x",),
                 (cum, dt, x, b, c, s))[0]


def _output_fwd(cum, dt, x, b, c, s, chunk, precision, interpret):
    return (_output(cum, dt, x, b, c, s, chunk, precision, interpret),
            (cum, dt, x, b, c, s))


def _output_bwd(chunk, precision, interpret, res, dy):
    cum, dt, x, b, c, s = res
    dx, ddt, dcum, db, dc, ds = _call(
        _output_bwd_kernel, x, b, chunk, precision, interpret,
        ("rows", "rows", "x", "bc", "bc", "s", "x"),
        ("x", "rows", "rows", "bc", "bc", "s"), (cum, dt, x, b, c, s, dy))
    return dcum, ddt, dx.astype(x.dtype), db, dc, ds


_output.defvjp(_output_fwd, _output_bwd)


def _precision():
    """The products' precision, read from ``jax_default_matmul_precision``
    as XLA's einsums read it."""
    highest = jax.config.jax_default_matmul_precision in ("highest",
                                                          "float32")
    return lax.Precision.HIGHEST if highest else lax.Precision.DEFAULT


def chunk_states(cum, dt, x, b, *, chunk: int, interpret: bool = False):
    """The state each chunk adds, differentiable in every input.

    cum: (R, H, S) float32 cumulative log-decay, restarting at each chunk;
    dt: (R, H, S) float32 step sizes; x: (R, S, H·P) inputs, float32 or
    narrower, scaled by dt here; b: (R, S, N) float32. Returns (S/L, R,
    H·P, N) float32: per chunk Σ_l exp(cum_{L−1} − cum_l) Δt_l x_l ⊗ B_l."""
    return _states(cum, dt, x, b, chunk, _precision(), interpret)


def chunk_output(cum, dt, x, b, c, states, *, chunk: int,
                 interpret: bool = False):
    """Each chunk's output, differentiable in every input: the intra-chunk
    term plus exp(cum_l)·S·C_l with S the chunk's entering state.

    cum, dt, x, b as for ``chunk_states``; c: (R, S, N) float32; states:
    (S/L, R, H·P, N) float32, the state entering each chunk. Returns y
    (R, S, H·P) float32."""
    return _output(cum, dt, x, b, c, states, chunk, _precision(), interpret)


def ssd_intra_chunk(a, x, b, c, *, chunk: int, interpret: bool = False):
    """The intra-chunk term alone (no entering state), in
    ``kernels/ref.ssd_intra_ref``'s layout: a: (R, S, H) log-decay;
    x: (R, S, H, P), already Δt-scaled; b, c: (R, S, N). Returns
    (R, S, H, P) float32. The within-chunk cumulative decay is taken
    here, in JAX, so that its gradient flows back to ``a`` through
    ``cumsum``'s own VJP."""
    R, S, H, P = x.shape
    N = b.shape[-1]
    L = min(chunk, S)
    cum = jnp.cumsum(a.astype(jnp.float32).reshape(R, S // L, L, H), axis=2)
    f32 = lambda t: t.astype(jnp.float32)
    y = chunk_output(cum.reshape(R, S, H).transpose(0, 2, 1),
                     jnp.ones((R, H, S), jnp.float32), x.reshape(R, S, H * P),
                     f32(b), f32(c),
                     jnp.zeros((S // L, R, H * P, N), jnp.float32), chunk=L,
                     interpret=interpret)
    return y.reshape(R, S, H, P)
