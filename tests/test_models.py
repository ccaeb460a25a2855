"""Per-arch smoke tests (REDUCED configs, one fwd/train step on CPU, shape +
finiteness assertions) and serve-path equivalence."""
import warnings

warnings.filterwarnings("ignore")

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as tfm
from repro.models.common import init_params

ARCH_IDS = sorted(configs.ARCHS)


def _batch_for(cfg, key, B=2, S=24):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1),
             "mask": jnp.ones((B, S), jnp.float32)}
    if cfg.mrope_sections is not None:
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (3, B, S)).astype(jnp.int32)
    if cfg.patch_embed_tokens:
        batch["patch_embeds"] = 0.02 * jax.random.normal(
            key, (B, cfg.patch_embed_tokens, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_arch_smoke_forward_and_grad(arch_id):
    """Reduced config: loss finite, grads finite, logits shaped (B,S?,V)."""
    spec = configs.get(arch_id)
    cfg = spec.reduced
    params = init_params(tfm.model_defs(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype)
    batch = _batch_for(cfg, jax.random.PRNGKey(1))
    loss, metrics = tfm.lm_loss(cfg, params, batch)
    assert np.isfinite(float(loss)), (arch_id, loss)
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0
    h, _, _ = tfm.forward(cfg, params, batch["tokens"],
                          mrope_positions=batch.get("mrope_positions"),
                          patch_embeds=batch.get("patch_embeds"))
    assert h.shape == batch["tokens"].shape + (cfg.d_model,)
    logits = tfm.logits_at(cfg, params, h[:, -1])
    assert logits.shape == (batch["tokens"].shape[0], cfg.vocab_size)

    grads, _ = jax.grad(lambda p: tfm.lm_loss(cfg, p, batch),
                        has_aux=True)(params)
    for leaf in jax.tree_util.tree_leaves(grads):
        assert np.all(np.isfinite(np.asarray(leaf, np.float32))), arch_id


@pytest.mark.parametrize("arch_id", ["gemma3-4b", "mamba2-780m",
                                     "recurrentgemma-2b",
                                     "deepseek-v2-236b", "phi3-mini-3.8b"])
def test_decode_matches_teacher_forcing(arch_id):
    """prefill + step-by-step decode == full forward (fp32, no MoE drops)."""
    spec = configs.get(arch_id)
    cfg = dataclasses.replace(spec.reduced, compute_dtype=jnp.float32)
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = init_params(tfm.model_defs(cfg), jax.random.PRNGKey(0),
                         jnp.float32)
    B, S = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    h, _, _ = tfm.forward(cfg, params, tokens)
    ref = tfm.logits_at(cfg, params, h[:, -1])
    Sp = S - 4
    caches = tfm.init_caches(cfg, B, max_len=S)
    lg, caches = tfm.prefill(cfg, params, tokens[:, :Sp], caches)
    for t in range(Sp, S):
        pos = jnp.full((B,), t, jnp.int32)
        lg, caches = tfm.decode_step(cfg, params, tokens[:, t:t + 1],
                                     caches, pos)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("vocab", [300, 512, 1000])
def test_lm_loss_matches_dense_cross_entropy(vocab):
    """lm_loss's cross-entropy, chunked along S (three chunks here), and its
    gradient in the unembedding, against a dense log-softmax over the whole
    vocabulary, with some positions masked out."""
    cfg = dataclasses.replace(configs.get("phi3-mini-3.8b").reduced,
                              n_layers=1, vocab_size=vocab, loss_chunk=16,
                              compute_dtype=jnp.float32)
    params = init_params(tfm.model_defs(cfg), jax.random.PRNGKey(0),
                         jnp.float32)
    B, S = 2, 24                  # loss_chunk // B = 8 positions a chunk
    batch = _batch_for(cfg, jax.random.PRNGKey(1), B, S)
    batch["mask"] = batch["mask"].at[0, -5:].set(0.0).at[1, :3].set(0.0)
    h, _, _ = tfm.forward(cfg, params, batch["tokens"])

    def dense_ce(w):
        logp = jax.nn.log_softmax(jnp.einsum("bsd,dv->bsv", h, w), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(nll * batch["mask"]) / jnp.sum(batch["mask"])

    def chunked_ce(w):
        return tfm.lm_loss(cfg, {**params, "unembed": w}, batch)[1]["ce"]

    w = params["unembed"]
    assert w.shape == (cfg.d_model, vocab)
    got, got_dw = jax.value_and_grad(chunked_ce)(w)
    want, want_dw = jax.value_and_grad(dense_ce)(w)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_dw), np.asarray(want_dw),
                               rtol=1e-4,
                               atol=1e-5 * np.abs(np.asarray(want_dw)).max())


def test_all_cells_accounted():
    """40 assigned cells; skips only long_500k on pure full-attention archs."""
    cells = configs.cells()
    assert len(cells) == 40
    skipped = [(a, s) for a, s, ok in cells if not ok]
    assert all(s == "long_500k" for _, s in skipped)
    assert {a for a, _ in skipped} == {
        "qwen1.5-4b", "phi3-mini-3.8b", "qwen2-vl-72b", "musicgen-medium",
        "grok-1-314b", "deepseek-v2-236b"}
    assert sum(ok for _, _, ok in cells) == 34


def test_full_configs_match_assignment():
    """Pin the published numbers (guards accidental config drift)."""
    c = configs.get("gemma3-4b").config
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (34, 2560, 8, 4, 10240, 262144)
    c = configs.get("deepseek-v2-236b").config
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab_size) == \
        (60, 5120, 128, 102400)
    assert (c.moe.n_experts, c.moe.top_k, c.moe.n_shared) == (160, 6, 2)
    assert c.mla.kv_lora_rank == 512
    c = configs.get("grok-1-314b").config
    assert (c.n_layers, c.d_model, c.d_ff, c.moe.n_experts, c.moe.top_k) \
        == (64, 6144, 32768, 8, 2)
    c = configs.get("mamba2-780m").config
    assert (c.n_layers, c.d_model, c.ssm.d_state) == (48, 1536, 128)
    c = configs.get("qwen2-vl-72b").config
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff) == \
        (80, 8192, 64, 8, 29568)
    assert sum(c.mrope_sections) == c.head_dim // 2


def test_param_counts_close_to_published():
    """Total parameter counts should be near the nameplate sizes.
    (Re-implemented here — importing launch.dryrun would set XLA_FLAGS.)"""
    import jax as _jax

    def count(cfg):
        defs = tfm.model_defs(cfg)
        leaves = _jax.tree_util.tree_leaves(
            defs, is_leaf=lambda x: hasattr(x, "logical"))
        total = 0
        for d in leaves:
            n = 1
            for s in d.shape:
                n *= s
            total += n
        return total

    for arch_id, nominal, tol in [
        ("gemma3-4b", 4e9, 0.35), ("gemma3-27b", 27e9, 0.25),
        ("qwen1.5-4b", 4e9, 0.3), ("phi3-mini-3.8b", 3.8e9, 0.25),
        ("qwen2-vl-72b", 72e9, 0.25), ("mamba2-780m", 0.78e9, 0.3),
        ("recurrentgemma-2b", 2e9, 0.6), ("grok-1-314b", 314e9, 0.15),
        ("deepseek-v2-236b", 236e9, 0.15),
    ]:
        n = count(configs.get(arch_id).config)
        assert abs(n - nominal) / nominal < tol, (arch_id, n, nominal)
