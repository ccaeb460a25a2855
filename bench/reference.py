"""The plain reference of a training cell: float32 loss and gradients at
``precision=HIGHEST``, the elastic-averaging update written out leaf by
leaf, and the readings that ``check`` compares with the program's.

It imports nothing of the program and takes nothing that the program has
made. The weights come from the seed by the initialisation that the
configuration's file states (``init_params``), and the rows from ``gen``.
Each configuration's module (``configs/<name>.py``) supplies the model:
``layout`` (its leaves, in the order the program flattens them),
``loss_sum`` and ``ROWS_PER_BLOCK``.

``mode="fp8"`` is the control (``Precision``): the reference computed in
float8 e4m3 where the configurations state bfloat16, the step below it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0      # largest finite float8_e4m3fn


def init_params(layout, key):
    """The stated initialisation: per leaf, in layout order, a key from
    ``split(key, n_leaves)``; "normal" leaves are a truncated normal on
    [-2, 2] times 1/sqrt(shape[-2]) (shape[-1] for vectors), float32."""
    keys = jax.random.split(key, len(layout))
    out = {}
    for k, (name, shape, init) in zip(keys, layout):
        if init == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        elif init == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            out[name] = (jax.random.truncated_normal(
                k, -2.0, 2.0, shape, jnp.float32) / math.sqrt(max(fan_in, 1)))
    return out


# ---------------------------------------------------------------------------
# matmuls: float32 at HIGHEST, or the float8 control
# ---------------------------------------------------------------------------

def _fake_fp8(x):
    """x rounded to float8 e4m3 under one scale per tensor (amax / 448)."""
    s = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    # the clip makes the cast saturate: e4m3fn has no infinity, and a value
    # rounded past 448 would become NaN
    return (jnp.clip(x / s, -F8_MAX, F8_MAX).astype(jnp.float8_e4m3fn)
            .astype(jnp.float32) * s)


@jax.custom_vjp
def _fp8_in(x):
    """Rounds to float8 on the way in; passes the cotangent through."""
    return _fake_fp8(x)


_fp8_in.defvjp(lambda x: (_fake_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_both(x):
    """Rounds to float8 on the way in, and its cotangent on the way back:
    an activation stored in float8."""
    return _fake_fp8(x)


_fp8_both.defvjp(lambda x: (_fake_fp8(x), None), lambda _, g: (_fake_fp8(g),))


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


class Precision:
    """How the reference computes: ``mm(eq, a, b, wide=False)`` for every
    matmul and ``cast(x)`` wherever the program stores an activation in its
    compute dtype. "f32" is float32 at HIGHEST throughout. "fp8" is the
    control: matmul inputs and every activation the program keeps in
    bfloat16 are rounded to float8 e4m3 with one scale per tensor, and so
    are their cotangents; a matmul that the program accumulates into a
    float32 result (``wide``, the logits) keeps it."""

    def __init__(self, mode: str):
        if mode not in ("f32", "fp8"):
            raise ValueError(mode)
        self.mode = mode

    def mm(self, eq, a, b, wide=False):
        if self.mode == "f32":
            return _einsum(eq, a, b)
        out = _einsum(eq, _fp8_in(a), _fp8_in(b))
        return out if wide else _fp8_both(out)

    def cast(self, x):
        return x if self.mode == "f32" else _fp8_both(x)


# ---------------------------------------------------------------------------
# one worker's loss and gradient, in blocks of rows
# ---------------------------------------------------------------------------

class Reference:
    """The reference for one configuration and traffic mix. Jitted
    functions are built once per object and reused across workers, steps
    and seeds."""

    def __init__(self, model_mod, model: dict, traffic: dict,
                 mode: str = "f32"):
        self.mod, self.m, self.t = model_mod, model, traffic
        self.layout = model_mod.layout(model)
        prec = Precision(mode)

        def loss_fn(params, tokens, targets, mask):
            return model_mod.loss_sum(model, params, tokens, targets, mask,
                                      prec)

        def acc(params, g_acc, l_acc, tokens, targets, mask):
            l, g = jax.value_and_grad(loss_fn)(params, tokens, targets, mask)
            return (jax.tree_util.tree_map(jnp.add, g_acc, g), l_acc + l)

        self._acc = jax.jit(acc, donate_argnums=(1,))
        self._init = jax.jit(partial(init_params, self.layout))
        self._norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                                         for k, v in t.items()})
        self._dnorms = jax.jit(lambda a, b: {
            k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})

    def init(self, key, device):
        return jax.device_put(self._init(key), device)

    def grad(self, params, batch, rows=None):
        """(mean loss, gradient of the mean loss) over the rows given.
        ``rows`` keeps only the first rows (the half-batch fault)."""
        tok, tgt, msk = batch["tokens"], batch["targets"], batch["mask"]
        if rows is not None:
            tok, tgt, msk = tok[:rows], tgt[:rows], msk[:rows]
        step = self.mod.ROWS_PER_BLOCK
        g = jax.tree_util.tree_map(jnp.zeros_like, params)
        l = jnp.zeros((), jnp.float32)
        for r in range(0, tok.shape[0], step):
            g, l = self._acc(params, g, l, tok[r:r + step], tgt[r:r + step],
                             msk[r:r + step])
        n = float(np.sum(msk))
        return l / n, jax.tree_util.tree_map(lambda x: x / n, g)

    # -- the elastic-averaging SGD of the traffic mix, leaf by leaf --------
    def run(self, key, batches, devices, fault=None):
        """Follows ``len(batches)`` steps of Sync EASGD with momentum from
        the stated initialisation. ``batches[step][worker]`` are the rows.
        ``fault`` plants one of the faults a check must catch: "half" (each
        worker's mean over the first half of its rows only) or "noexchange"
        (no cross-worker sum: each worker takes its own weights as the
        mean and keeps its own center). Returns the readings."""
        t = self.t
        eta, rho, mu, tau = t["eta"], t["rho"], t["mu"], t["tau"]
        P = t["workers"]
        dev = [devices[i % len(devices)] for i in range(P)]
        p0 = [self.init(key, d) for d in dev]
        w = [jax.tree_util.tree_map(jnp.copy, p) for p in p0]
        v = [jax.tree_util.tree_map(jnp.zeros_like, p) for p in p0]
        c = [jax.tree_util.tree_map(jnp.copy, p) for p in p0]
        rows = t["batch_per_worker"] // 2 if fault == "half" else None
        losses, grad_norms = [], None
        for step, per_worker in enumerate(batches):
            # dispatch every worker before reading any result: the workers'
            # devices run in parallel
            out = [self.grad(w[i], per_worker[i], rows) for i in range(P)]
            losses.append(float(np.mean([float(l) for l, _ in out])))
            g = [gi for _, gi in out]
            if step == 0:
                grad_norms = [self._norms(gi) for gi in g]
            v = [jax.tree_util.tree_map(lambda v_, g_: mu * v_ - eta * g_,
                                        v[i], g[i]) for i in range(P)]
            if step % tau:
                w = [jax.tree_util.tree_map(jnp.add, w[i], v[i])
                     for i in range(P)]
                continue
            a = eta * rho
            if fault == "noexchange":
                means = [jax.tree_util.tree_map(
                    lambda w_, c_: c_ + (w_ - c_) / P, w[i], c[i])
                    for i in range(P)]
            else:
                mean = {k: sum(jax.device_put(w[i][k], dev[0])
                               for i in range(P)) / P for k in w[0]}
                means = [jax.device_put(mean, d) for d in dev]
                del mean
            w = [jax.tree_util.tree_map(
                lambda w_, v_, c_: w_ + v_ - a * (w_ - c_), w[i], v[i], c[i])
                for i in range(P)]
            c = [jax.tree_util.tree_map(
                lambda c_, m_: c_ + a * P * (m_ - c_), c[i], means[i])
                for i in range(P)]
            del means
        changes = [self._dnorms(w[i], p0[i]) for i in range(P)]
        center = self._dnorms(c[0], p0[0])
        names = [n for n, _, _ in self.layout]
        get = lambda d: [float(d[n]) for n in names]
        return {"loss": losses,
                "grad": [get(gn) for gn in grad_norms],
                "change": [get(ch) for ch in changes],
                "center_change": get(center)}
