"""The model zoo as PS problems: real architectures on the PS wire.

`repro.models` + `repro.configs` define the paper-scale architectures
(transformers, MoE, SSM — reduced configs sized for CPU smoke tests);
this module packages them as ``ProblemSpec`` factories so the parameter-
server runtime can train them over any transport — including the TCP p2p
data plane, where a multi-MB flat parameter row is exactly what the
bucketed overlap exchange exists for.

Every factory attaches ``grad_fn.layer_sizes`` — the per-leaf element
counts of the parameter pytree in ravel order. That is the layer structure
``comm.rounds.default_bucket_boundaries`` cuts the exchange row on: bucket
edges land on real layer edges, the §5.2 packed-layout analogue of
NCCL-style gradient bucketing.

Spawn safety follows ``make_jax_mlp``: the platform is gated to CPU
BEFORE the first jax import, so remote/spawned workers rebuild the model
without grabbing an accelerator. Worker-private minibatch RNG streams are
keyed by worker id (one draw per call), preserving the determinism
contract the bitwise cross-checks rely on.
"""
from __future__ import annotations

import numpy as np

from repro.ps.problems import (NUMPY_MLP, NUMPY_MLP_LARGE, NUMPY_MLP_MED,
                               ProblemSpec, spec)


def _gate_cpu():
    import os
    import sys
    if "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"


def _layer_sizes(params) -> list[int]:
    """Per-leaf element counts in ravel_pytree order (= tree_leaves order)."""
    import jax
    return [int(np.prod(leaf.shape)) if leaf.shape else 1
            for leaf in jax.tree_util.tree_leaves(params)]


# ---------------------------------------------------------------------------
# decoder LMs (transformer / MoE / SSM) — any repro.configs arch id
# ---------------------------------------------------------------------------

def make_zoo_lm(arch: str = "gemma3-4b", seq: int = 24, batch: int = 2,
                seed: int = 0):
    """A reduced-config decoder LM from the arch registry as a PS problem:
    next-token loss on synthetic token streams. ``arch`` is any
    ``repro.configs.ARCHS`` id — that includes the MoE (deepseek-v2,
    grok-1) and SSM/recurrent (mamba2, recurrentgemma) families, so the
    whole zoo flows through one factory. The flat f64 row is the
    ravel_pytree packing of the init params (hundreds of KB to several MB
    depending on the arch — real multi-frame streaming on the TCP wire)."""
    _gate_cpu()
    import jax
    import jax.numpy as jnp
    from jax import flatten_util

    from repro import configs
    from repro.models import transformer as tfm
    from repro.models.common import init_params

    cfg = configs.get(arch).reduced
    params = init_params(tfm.model_defs(cfg), jax.random.PRNGKey(seed),
                         jnp.float32)
    flat, unravel = flatten_util.ravel_pytree(params)
    sizes = _layer_sizes(params)

    def _loss(w32, tokens, targets, mask):
        batch_d = {"tokens": tokens, "targets": targets, "mask": mask}
        if cfg.mrope_sections is not None:
            S = tokens.shape[1]
            batch_d["mrope_positions"] = jnp.broadcast_to(
                jnp.arange(S)[None, None],
                (3, tokens.shape[0], S)).astype(jnp.int32)
        return tfm.lm_loss(cfg, unravel(w32), batch_d)[0]

    gfn = jax.jit(jax.grad(_loss))
    lfn = jax.jit(_loss)

    def _tokens(rng):
        t = rng.randint(0, cfg.vocab_size, size=(batch, seq + 1))
        return (jnp.asarray(t[:, :-1]), jnp.asarray(t[:, 1:]),
                jnp.ones((batch, seq), jnp.float32))

    rngs: dict = {}

    def grad_fn(w, step, worker):
        rng = rngs.setdefault(worker, np.random.RandomState(1000 + worker))
        tok, tgt, mask = _tokens(rng)
        return np.asarray(gfn(jnp.asarray(w, jnp.float32), tok, tgt, mask),
                          np.float64)

    eval_rng = np.random.RandomState(seed + 7)
    eval_batch = _tokens(eval_rng)

    def eval_fn(w):
        return float(lfn(jnp.asarray(w, jnp.float32), *eval_batch))

    grad_fn.layer_sizes = sizes
    return np.asarray(flat, np.float64), grad_fn, eval_fn


# ---------------------------------------------------------------------------
# CNNs — the paper's image models (LeNet / AlexNet shapes)
# ---------------------------------------------------------------------------

def make_zoo_cnn(model: str = "lenet", seed: int = 0, n_train: int = 512,
                 n_test: int = 256, batch: int = 8, noise: float = 1.6):
    """LeNet on 28×28×1 or AlexNet on 32×32×3 Gaussian-mixture images —
    the paper's CIFAR/MNIST-shaped workloads as PS problems."""
    _gate_cpu()
    import jax
    import jax.numpy as jnp
    from jax import flatten_util

    from repro.data.synthetic import make_classification_dataset
    from repro.models import cnn

    if model == "lenet":
        shape, init, apply = (28, 28, 1), cnn.lenet_init, cnn.lenet_apply
    elif model == "alexnet":
        shape, init, apply = (32, 32, 3), cnn.alexnet_init, cnn.alexnet_apply
    else:
        raise ValueError(f"unknown cnn '{model}' (lenet/alexnet)")
    x, y = make_classification_dataset(n_train + n_test, shape=shape,
                                       n_classes=10, noise=noise, seed=seed)
    xtr, ytr = x[:n_train], y[:n_train]
    xte, yte = x[n_train:], y[n_train:]
    params = init(jax.random.PRNGKey(seed))
    flat, unravel = flatten_util.ravel_pytree(params)
    sizes = _layer_sizes(params)

    @jax.jit
    def loss_flat(w32, xb, yb):
        return cnn.xent_loss(apply(unravel(w32), xb), yb)

    gfn = jax.jit(jax.grad(loss_flat))

    @jax.jit
    def err_flat(w32):
        return 1.0 - cnn.accuracy(apply(unravel(w32), xte), yte)

    rngs: dict = {}

    def grad_fn(w, step, worker):
        rng = rngs.setdefault(worker, np.random.RandomState(1000 + worker))
        idx = rng.randint(0, n_train, size=batch)
        return np.asarray(gfn(jnp.asarray(w, jnp.float32), xtr[idx],
                              ytr[idx]), np.float64)

    def eval_fn(w):
        return float(err_flat(jnp.asarray(w, jnp.float32)))

    grad_fn.layer_sizes = sizes
    return np.asarray(flat, np.float64), grad_fn, eval_fn


# ---------------------------------------------------------------------------
# the named zoo — what `--model` resolves (launch/train, launch/cluster)
# ---------------------------------------------------------------------------

def zoo_names() -> list[str]:
    from repro import configs
    return (["tiny-mlp", "mlp-large", "jax-mlp", "lenet", "alexnet"]
            + sorted(configs.ARCHS))


def resolve(name: str) -> ProblemSpec:
    """``--model`` name -> ProblemSpec. MLP names map to the seed problems
    (tiny-mlp is the default everywhere — nothing changes without the
    flag); arch ids map to ``make_zoo_lm``; lenet/alexnet to the CNNs."""
    fixed = {"tiny-mlp": NUMPY_MLP_MED, "mlp": NUMPY_MLP,
             "mlp-large": NUMPY_MLP_LARGE,
             "jax-mlp": spec("repro.ps.problems:make_jax_mlp")}
    if name in fixed:
        return fixed[name]
    if name in ("lenet", "alexnet"):
        return spec("repro.ps.zoo:make_zoo_cnn", model=name)
    from repro import configs
    if name in configs.ARCHS:
        return spec("repro.ps.zoo:make_zoo_lm", arch=name)
    raise ValueError(f"unknown model '{name}'; have: {zoo_names()}")
