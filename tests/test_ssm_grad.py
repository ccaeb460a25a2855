"""The Mamba-2 loss's gradient at the published SSD chunk of 256, against the
plain float32 reference of the benchmark's ``mamba2_780m`` configuration.

Inside a chunk the scan weighs position m's input at position l by
exp(cum_l - cum_m). Above the diagonal (l < m) that exponent is positive,
the sum of |dt * A| over up to 255 positions, and past float32's 88.7 its
exp is inf: the masked entries must be masked before ``exp``, or the
backward pass multiplies 0 by inf. Tiny widths, but the published chunk
and a sequence of two chunks, with step sizes large enough that the
exponent passes 88.7.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec
from repro import configs
from repro.models import ssm
from repro.models import transformer as tfm
from repro.models.common import SSMConfig, init_params

CHUNK, SEQ, ROWS = 256, 512, 2
# float32 on both sides, summed in different orders (the program carries
# the state chunk to chunk, the reference sums every chunk's state at
# once): each leaf's gradient agrees to 2e-5 of its norm or better, A_log's
# (a sum over every position) the widest
TOL = 1e-4
F32_EXP_MAX = float(np.log(np.finfo(np.float32).max))    # 88.72
DT_BIAS = 1.0             # softplus(1 + small) ~ 1.3 per position


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(
        configs.get("mamba2-780m").reduced, n_layers=2, d_model=32,
        vocab_size=64, compute_dtype=jnp.float32,
        ssm=SSMConfig(d_state=16, head_dim=16, expand=2, d_conv=4,
                      chunk=CHUNK))
    m = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
         "vocab_size": cfg.vocab_size, "ssm": dataclasses.asdict(cfg.ssm)}
    mod = spec.config_module({"configs": [
        {"name": "mamba2_780m", "file": "bench/configs/mamba2_780m.json"}]},
        "mamba2_780m")
    params = init_params(tfm.model_defs(cfg), jax.random.PRNGKey(3),
                         jnp.float32)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + DT_BIAS
        if jax.tree_util.keystr(path).endswith("['dt_bias']") else x, params)
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    batch = {"tokens": jax.random.randint(k1, (ROWS, SEQ), 0, 64),
             "targets": jax.random.randint(k2, (ROWS, SEQ), 0, 64),
             "mask": jnp.ones((ROWS, SEQ), jnp.float32)}
    return cfg, m, mod, params, batch


def _by_name(mod, m, params):
    names = [n for n, _, _ in mod.layout(m)]
    return dict(zip(names, jax.tree_util.tree_leaves(params)))


def test_unmasked_exponent_passes_float32_range(model, monkeypatch):
    """The case the test is for: on the step sizes that the program's own
    mixer hands its scan, the sum of dt * |A| over one chunk passes 88.7,
    so exp of it is inf in float32."""
    cfg, m, mod, params, batch = model
    spans, scan = [], ssm._ssd_chunked

    def recording(xh, dt, A, *args, **kwargs):
        a = (dt * -A).reshape(dt.shape[0], -1, CHUNK, dt.shape[-1])
        jax.debug.callback(spans.append, jnp.max(jnp.sum(a[:, :, 1:], 2)))
        return scan(xh, dt, A, *args, **kwargs)

    monkeypatch.setattr(ssm, "_ssd_chunked", recording)
    jax.block_until_ready(tfm.lm_loss(cfg, params, batch))
    assert len(spans) == cfg.n_layers
    assert max(float(s) for s in spans) > F32_EXP_MAX


def test_grad_finite_and_matches_reference_at_chunk_256(model):
    cfg, m, mod, params, batch = model

    def program(prm):
        return tfm.lm_loss(cfg, prm, batch)[0]

    def ref(prm):
        n = jnp.sum(batch["mask"])
        return mod.loss_sum(m, _by_name(mod, m, prm), batch["tokens"],
                            batch["targets"], batch["mask"],
                            reference.Precision("f32")) / n

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(program))(params)
        lr, gr = jax.jit(jax.value_and_grad(ref))(params)
    assert np.isfinite(float(lp))
    assert abs(float(lp) - float(lr)) < TOL * abs(float(lr))
    gaps = {}
    for name, a, b in zip(_by_name(mod, m, gp),
                          jax.tree_util.tree_leaves(gp),
                          jax.tree_util.tree_leaves(gr)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.all(np.isfinite(a)), name
        gaps[name] = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    assert max(gaps.values()) < TOL, gaps


def test_kernel_path_grad_finite_and_matches_jnp_path_at_chunk_256(
        model, monkeypatch):
    """The scan as a TPU lowering runs it, in the Pallas chunk kernels and
    their backward kernels (interpreted here), on the same model, batch and
    step sizes: every gradient finite, and each leaf within TOL of the jnp
    path's."""
    cfg, m, mod, params, batch = model

    def program(prm):
        return tfm.lm_loss(cfg, prm, batch)[0]

    with jax.default_matmul_precision("highest"):
        lj, gj = jax.jit(jax.value_and_grad(program))(params)
        monkeypatch.setattr(ssm, "ssd_scan", functools.partial(
            ssm._ssd_chunked_kernel, placement=(None, None), interpret=True))
        lk, gk = jax.jit(jax.value_and_grad(program))(params)
    assert abs(float(lk) - float(lj)) < TOL * abs(float(lj))
    for name, a, b in zip(_by_name(mod, m, gk), jax.tree_util.tree_leaves(gk),
                          jax.tree_util.tree_leaves(gj)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.all(np.isfinite(a)), name
        assert np.linalg.norm(a - b) < TOL * np.linalg.norm(b), name
