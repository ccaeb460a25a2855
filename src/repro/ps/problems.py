"""Picklable, numpy-only training problems for the repro.ps runtime.

The multiprocessing transport spawns workers with a fresh interpreter, so a
problem is described by a ``ProblemSpec`` (dotted factory path + kwargs) and
REBUILT inside each worker — no jax import in children, no pickling of
jitted closures. The thread transport accepts either a spec or a prebuilt
``(w0, grad_fn, eval_fn)`` triple (e.g. ``benchmarks.common.make_mlp_problem``,
which is jax-backed).

Contract (same as ``core.async_engine.PSEngine``):
    grad_fn(w_flat, step, worker) -> grad_flat   # float64
    eval_fn(w_flat) -> scalar metric             # e.g. test error

Worker-private minibatch RNG streams are keyed by the worker id and advance
one draw per call — so two independently-built instances of the same spec
feed IDENTICAL gradients to the DES simulator and the real runtime whenever
the per-worker call orders match. That is the substrate of the DES↔real
bitwise cross-check.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from repro.data.synthetic import make_classification_dataset


@dataclasses.dataclass(frozen=True)
class ProblemSpec:
    """factory = "module:function"; building imports the module and calls
    ``function(**kwargs)`` -> (w0, grad_fn, eval_fn)."""

    factory: str
    kwargs: tuple = ()        # tuple of (key, value) pairs — hashable/picklable

    def build(self):
        mod_name, fn_name = self.factory.split(":")
        fn = getattr(importlib.import_module(mod_name), fn_name)
        return fn(**dict(self.kwargs))


def spec(factory: str, **kwargs) -> ProblemSpec:
    return ProblemSpec(factory=factory, kwargs=tuple(sorted(kwargs.items())))


# ---------------------------------------------------------------------------
# numpy MLP classification (manual backprop — no jax anywhere)
# ---------------------------------------------------------------------------

def _mlp_shapes(d_in, d_hidden, n_classes):
    return ((d_in, d_hidden), (d_hidden,), (d_hidden, n_classes),
            (n_classes,))


def _unpack(w, shapes):
    out, off = [], 0
    for s in shapes:
        size = int(np.prod(s))
        out.append(w[off:off + size].reshape(s))
        off += size
    return out


def make_numpy_mlp(seed: int = 0, n_train: int = 2048, n_test: int = 512,
                   d_in: int = 32, d_hidden: int = 32, n_classes: int = 4,
                   batch: int = 16, noise: float = 1.6):
    """One-hidden-layer tanh MLP on the Gaussian-mixture task; gradients by
    hand so worker processes never touch jax. Returns (w0, grad_fn, eval_fn)
    with w0 float64 flat."""
    x, y = make_classification_dataset(n_train + n_test, shape=(d_in,),
                                       n_classes=n_classes, noise=noise,
                                       seed=seed)
    x = x.astype(np.float64)
    xtr, ytr = x[:n_train], y[:n_train]
    xte, yte = x[n_train:], y[n_train:]
    shapes = _mlp_shapes(d_in, d_hidden, n_classes)
    rng = np.random.RandomState(seed + 1)
    w0 = np.concatenate([
        (rng.randn(*s) / np.sqrt(max(s[0], 1) if len(s) > 1 else 1)
         ).reshape(-1)
        for s in shapes]).astype(np.float64)

    def forward(w, xb):
        w1, b1, w2, b2 = _unpack(w, shapes)
        h = np.tanh(xb @ w1 + b1)
        return h, h @ w2 + b2

    rngs = {}

    def grad_fn(w, step, worker):
        r = rngs.setdefault(worker, np.random.RandomState(1000 + worker))
        idx = r.randint(0, n_train, size=batch)
        xb, yb = xtr[idx], ytr[idx]
        w1, b1, w2, b2 = _unpack(w, shapes)
        h, logits = forward(w, xb)
        logits -= logits.max(axis=1, keepdims=True)
        p = np.exp(logits)
        p /= p.sum(axis=1, keepdims=True)
        p[np.arange(batch), yb] -= 1.0
        p /= batch                              # d loss / d logits
        dw2 = h.T @ p
        db2 = p.sum(axis=0)
        dh = (p @ w2.T) * (1.0 - h * h)
        dw1 = xb.T @ dh
        db1 = dh.sum(axis=0)
        return np.concatenate([dw1.reshape(-1), db1, dw2.reshape(-1), db2])

    def eval_fn(w):
        _, logits = forward(w, xte)
        return float(np.mean(logits.argmax(axis=1) != yte))

    # layer structure for the bucketed exchange (bucket cuts land on layer
    # edges — comm.rounds.default_bucket_boundaries)
    grad_fn.layer_sizes = [int(np.prod(s)) for s in shapes]
    return w0, grad_fn, eval_fn


NUMPY_MLP = spec("repro.ps.problems:make_numpy_mlp")

# the BENCH_ps_runtime problem (~9k params, ~70 KB packed): small enough
# that this box's compute noise stays small in absolute terms, while the
# emulated wire (costmodel.PS_WIRE) prices its full-model message at a few
# ms — the paper's comm/compute regime
NUMPY_MLP_MED = spec("repro.ps.problems:make_numpy_mlp",
                     d_in=64, d_hidden=128, batch=32, n_train=4096,
                     n_test=1024, n_classes=4)

# a bandwidth-heavy variant (~68k params, ~0.5 MB packed) for experiments
# where the exchange should cost real memory bandwidth
NUMPY_MLP_LARGE = spec("repro.ps.problems:make_numpy_mlp",
                       d_in=128, d_hidden=512, batch=32, n_train=4096,
                       n_test=1024, n_classes=4)


# ---------------------------------------------------------------------------
# jax-backed problem, spawn-safe: the factory gates the platform BEFORE the
# first jax import, so spawned/remote workers rebuild it on CPU without
# grabbing an accelerator (and without re-initializing the parent's devices)
# ---------------------------------------------------------------------------

def make_jax_mlp(seed: int = 0, n_train: int = 2048, n_test: int = 512,
                 d_in: int = 32, d_hidden: int = 64, n_classes: int = 4,
                 batch: int = 16, noise: float = 1.6, depth: int = 2):
    """The thread transport's jax closures, packaged as a ``ProblemSpec``
    factory so PROCESS and TCP workers can run jax-backed problems too:
    same jit/grad structure as ``benchmarks.common.make_mlp_problem`` (f32
    compute inside jit, float64 at the runtime boundary — no global x64
    flip), but rebuildable from a dotted path inside a fresh interpreter.

    Platform gate: a spawned child must never race the parent for a GPU/TPU,
    so if this process hasn't initialized jax yet we pin it to CPU.
    """
    import os
    import sys
    if "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from jax import flatten_util

    from repro.models import cnn

    x, y = make_classification_dataset(n_train + n_test, shape=(d_in,),
                                       n_classes=n_classes, noise=noise,
                                       seed=seed)
    xtr, ytr = x[:n_train], y[:n_train]
    xte, yte = x[n_train:], y[n_train:]
    params = cnn.mlp_init(jax.random.PRNGKey(seed), d_in=d_in,
                          d_hidden=d_hidden, depth=depth,
                          n_classes=n_classes)
    flat, unravel = flatten_util.ravel_pytree(params)

    @jax.jit
    def loss_flat(w, xb, yb):
        return cnn.xent_loss(cnn.mlp_apply(unravel(w), xb), yb)

    gfn = jax.jit(jax.grad(loss_flat))

    @jax.jit
    def err_flat(w):
        return 1.0 - cnn.accuracy(cnn.mlp_apply(unravel(w), xte), yte)

    rngs = {}

    def grad_fn(w, step, worker):
        rng = rngs.setdefault(worker, np.random.RandomState(1000 + worker))
        idx = rng.randint(0, n_train, size=batch)
        return np.asarray(gfn(jnp.asarray(w, jnp.float32), xtr[idx],
                              ytr[idx]), np.float64)

    def eval_fn(w):
        return float(err_flat(jnp.asarray(w, jnp.float32)))

    grad_fn.layer_sizes = [
        int(np.prod(leaf.shape)) if leaf.shape else 1
        for leaf in jax.tree_util.tree_leaves(params)]
    return np.asarray(flat, np.float64), grad_fn, eval_fn


JAX_MLP = spec("repro.ps.problems:make_jax_mlp")
