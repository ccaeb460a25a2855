"""Decoder-LM assembly: heterogeneous layer patterns under lax.scan.

A model is ``cfg.pattern`` cycled ``n_periods`` times (plus an unrolled
remainder): e.g. gemma-3 is ("local",)*5 + ("attn",), recurrentgemma is
("rglru", "rglru", "attn"). Per pattern-slot the layer params are STACKED on
a leading (n_periods,) dim and the whole period is one ``lax.scan`` body —
HLO stays small for 80-layer models and remat applies per period.

Public surface (used by runtime / launch / tests):
  model_defs(cfg)                         — ParamDef pytree
  forward(cfg, params, tokens, ...)       — hidden states (+ caches)
  lm_loss(cfg, params, batch)             — scalar loss + metrics
  init_cache_defs(cfg, B, max_len)        — abstract cache pytree
  prefill(cfg, params, tokens, caches)    — logits of last pos + filled caches
  decode_step(cfg, params, tok, caches, pos) — next-token logits + caches
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention, ffn as ffn_lib, mla, moe as moe_lib
from repro.models import rglru as rglru_lib, sctx, ssm as ssm_lib
from repro.models.common import ModelConfig, ParamDef, rms_norm, softcap


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------

def _block_defs(cfg: ModelConfig, kind: str) -> dict:
    d = cfg.d_model
    out = {"norm1": ParamDef((d,), ("embed",), init="zeros")}
    if kind in ("attn", "local"):
        out["attn"] = attention.attention_defs(cfg)
    elif kind == "mla":
        out["attn"] = mla.mla_defs(cfg)
    elif kind == "ssm":
        out["ssm"] = ssm_lib.ssm_defs(cfg)
        return out                                   # mamba: no separate FFN
    elif kind == "rglru":
        out["rec"] = rglru_lib.rglru_defs(cfg)
    else:
        raise ValueError(f"unknown layer kind {kind}")
    out["norm2"] = ParamDef((d,), ("embed",), init="zeros")
    if cfg.moe is not None:
        out["moe"] = moe_lib.moe_defs(cfg)
    else:
        out["ffn"] = ffn_lib.ffn_defs(cfg)
    return out


def _stack_defs(defs, n: int):
    return jax.tree_util.tree_map(
        lambda p: ParamDef((n,) + p.shape, ("layers",) + p.logical, p.init,
                           p.scale),
        defs, is_leaf=lambda x: isinstance(x, ParamDef),
    )


def model_defs(cfg: ModelConfig) -> dict:
    d, V = cfg.d_model, cfg.vocab_size
    defs = {
        "embed": ParamDef((V, d), ("vocab", "embed")),
        "final_norm": ParamDef((d,), ("embed",), init="zeros"),
        "blocks": tuple(
            _stack_defs(_block_defs(cfg, kind), cfg.n_periods)
            for kind in cfg.pattern
        ),
        "rem": tuple(_block_defs(cfg, kind) for kind in cfg.remainder_kinds),
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((d, V), ("embed", "vocab"))
    return defs


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _cache_def_one(cfg: ModelConfig, kind: str, B: int, max_len: int):
    cd = cfg.compute_dtype
    D = cfg.resolved_head_dim
    if kind == "attn":
        S = max_len
        return {"k": jax.ShapeDtypeStruct((B, S, cfg.n_kv_heads, D), cd),
                "v": jax.ShapeDtypeStruct((B, S, cfg.n_kv_heads, D), cd)}
    if kind == "local":
        S = min(cfg.window, max_len)
        return {"k": jax.ShapeDtypeStruct((B, S, cfg.n_kv_heads, D), cd),
                "v": jax.ShapeDtypeStruct((B, S, cfg.n_kv_heads, D), cd)}
    if kind == "mla":
        a = cfg.mla
        return {"ckv": jax.ShapeDtypeStruct((B, max_len, a.kv_lora_rank), cd),
                "kpe": jax.ShapeDtypeStruct((B, max_len, a.qk_rope_head_dim),
                                            cd)}
    if kind == "ssm":
        s = cfg.ssm
        d_inner = s.expand * cfg.d_model
        H = d_inner // s.head_dim
        conv_dim = d_inner + 2 * s.d_state
        return {"conv": jax.ShapeDtypeStruct((B, s.d_conv - 1, conv_dim), cd),
                "state": jax.ShapeDtypeStruct((B, H, s.head_dim, s.d_state),
                                              jnp.float32)}
    if kind == "rglru":
        g = cfg.rglru
        return {"conv": jax.ShapeDtypeStruct((B, g.d_conv - 1, g.width), cd),
                "state": jax.ShapeDtypeStruct((B, g.width), jnp.float32)}
    raise ValueError(kind)


def init_cache_defs(cfg: ModelConfig, B: int, max_len: int):
    """Abstract cache pytree: (per-slot stacked, remainder list)."""
    def stack(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((cfg.n_periods,) + s.shape,
                                           s.dtype), tree)
    stacked = tuple(stack(_cache_def_one(cfg, kind, B, max_len))
                    for kind in cfg.pattern)
    rem = tuple(_cache_def_one(cfg, kind, B, max_len)
                for kind in cfg.remainder_kinds)
    return {"stacked": stacked, "rem": rem}


def init_caches(cfg: ModelConfig, B: int, max_len: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        init_cache_defs(cfg, B, max_len))


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------

_MIXERS = {
    "attn": attention.attention_block,
    "local": attention.attention_block,
    "mla": mla.mla_block,
    "ssm": ssm_lib.ssm_block,
    "rglru": rglru_lib.rglru_block,
}


def _apply_block(cfg: ModelConfig, kind: str, p, x, positions, cache,
                 cache_pos, mrope_positions):
    key = {"attn": "attn", "local": "attn", "mla": "attn",
           "ssm": "ssm", "rglru": "rec"}[kind]
    mixer = _MIXERS[kind]
    h = rms_norm(x, p["norm1"])
    kwargs = dict(cache=cache, cache_pos=cache_pos)
    if kind in ("attn", "local"):
        kwargs.update(kind=kind, mrope_positions=mrope_positions)
    y, new_cache = mixer(cfg, p[key], h, positions, **kwargs)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        return x, new_cache, aux
    h2 = rms_norm(x, p["norm2"])
    if cfg.moe is not None:
        y2, aux = moe_lib.moe_block(cfg, p["moe"], h2)
    else:
        y2 = ffn_lib.ffn_block(cfg, p["ffn"], h2)
    return x + y2, new_cache, aux


def _remat_wrap(cfg: ModelConfig, fn, training: bool):
    # per-SLOT checkpointing inside period_body already bounds residuals to
    # one layer; an additional period-level checkpoint only adds recompute.
    return fn


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params, tokens, *, positions=None, caches=None,
            cache_pos=None, mrope_positions=None, patch_embeds=None,
            constrain=None):
    """tokens: (B, S) int32. Returns (hidden (B,S,d), new_caches, aux_loss).

    ``constrain(kind, params_subtree)`` (optional): re-shards one layer's
    sliced params before use — the runtime passes a gather-to-compute-layout
    constraint here, which is how streaming FSDP/ZeRO-3 is made explicit
    (one all-gather per layer per pass instead of GSPMD choosing to
    all-reduce activations).
    """
    cd = cfg.compute_dtype
    B, S = tokens.shape
    h = sctx.shard(jnp.take(params["embed"], tokens, axis=0).astype(cd),
                   "batch", "seq", "embed")
    if patch_embeds is not None:
        P_ = patch_embeds.shape[1]
        h = jnp.concatenate([patch_embeds.astype(cd), h[:, P_:]], axis=1)
    if positions is None:
        if cache_pos is not None and S == 1:
            positions = cache_pos[:, None]
        else:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    training = caches is None
    aux_total = jnp.zeros((), jnp.float32)

    def one_block(kind, p_s, x, cache):
        p_s = constrain(kind, p_s) if constrain else p_s
        x, nc, a = _apply_block(cfg, kind, p_s, x, positions, cache,
                                cache_pos, mrope_positions)
        return sctx.shard(x, "batch", "seq", "embed"), nc, a

    def period_body(carry, xs):
        x, aux = carry
        slot_params = xs[0] if caches is not None else xs
        slot_caches = xs[1] if caches is not None else (None,) * len(cfg.pattern)
        new_slot_caches = []
        for s, kind in enumerate(cfg.pattern):
            # per-SLOT remat: backward holds one layer's residuals at a time
            # even when the pattern period contains several layers.
            blk = partial(one_block, kind)
            if training and cfg.remat != "none":
                blk = jax.checkpoint(blk, static_argnums=())
            x, nc, a = blk(slot_params[s], x, slot_caches[s])
            aux = aux + a
            new_slot_caches.append(nc)
        ys = tuple(new_slot_caches) if caches is not None else 0
        return (x, aux), ys

    body = _remat_wrap(cfg, period_body, training)
    if cfg.n_periods:
        xs = (params["blocks"], caches["stacked"]) if caches is not None \
            else params["blocks"]
        (h, aux_total), new_stacked = lax.scan(
            body, (h, aux_total), xs)
    else:
        new_stacked = caches["stacked"] if caches is not None else ()

    new_rem = []
    for i, kind in enumerate(cfg.remainder_kinds):
        c = caches["rem"][i] if caches is not None else None
        p_i = constrain(kind, params["rem"][i]) if constrain else \
            params["rem"][i]
        h, nc, a = _apply_block(cfg, kind, p_i, h, positions,
                                c, cache_pos, mrope_positions)
        aux_total = aux_total + a
        new_rem.append(nc)

    h = rms_norm(h, params["final_norm"])
    new_caches = None
    if caches is not None:
        new_caches = {"stacked": new_stacked, "rem": tuple(new_rem)}
    return h, new_caches, aux_total


# ---------------------------------------------------------------------------
# LM head / loss
# ---------------------------------------------------------------------------

def _unembed_weight(cfg: ModelConfig, params):
    if cfg.tie_embeddings:
        return params["embed"].T                      # (d, V)
    return params["unembed"]


def logits_at(cfg: ModelConfig, params, h):
    """Logits for the given hidden states (use on a few positions only)."""
    w = _unembed_weight(cfg, params)
    out = jnp.einsum("...d,dv->...v", h.astype(jnp.float32),
                     w.astype(jnp.float32))
    return softcap(out, cfg.logit_softcap)


def _divisor_chunk(T: int, want: int) -> int:
    c = min(want, T)
    while T % c:
        c -= 1
    return max(c, 1)


def lm_loss(cfg: ModelConfig, params, batch, extra_fwd_kwargs=None):
    """Next-token cross-entropy, chunked along the SEQUENCE dim so the
    (B, S, V) logits never fully materialize (262k vocab × 1M tokens would
    be TBs of HBM). Chunking along S keeps the batch dim — and therefore
    the `data` sharding — intact on every chunk (chunking flat tokens would
    split across data shards and force replication).

    batch: {tokens (B,S), targets (B,S), mask (B,S)} + modality extras.
    """
    kwargs = dict(extra_fwd_kwargs or {})
    for k in ("mrope_positions", "patch_embeds"):
        if k in batch:
            kwargs[k] = batch[k]
    h, _, aux = forward(cfg, params, batch["tokens"], **kwargs)
    B, S, d = h.shape
    w = _unembed_weight(cfg, params)
    mask = batch["mask"].astype(jnp.float32)

    Cs = _divisor_chunk(S, max(1, cfg.loss_chunk // B))
    nc = S // Cs

    @jax.named_scope("loss")
    def chunk_fn(carry, inp):
        h_c, t_c, m_c = inp                     # (B,Cs,d), (B,Cs), (B,Cs)
        logits = jnp.einsum("bcd,dv->bcv", h_c, w.astype(h_c.dtype),
                            preferred_element_type=jnp.float32)
        logits = sctx.shard(logits, "batch", "seq", "vocab")
        logits = softcap(logits, cfg.logit_softcap)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, t_c[..., None], axis=2)[..., 0]
        loss_sum, n_tok, correct = carry
        pred = jnp.argmax(logits, axis=-1)
        correct = correct + jnp.sum((pred == t_c) * m_c)
        return (loss_sum + jnp.sum((logz - ll) * m_c),
                n_tok + jnp.sum(m_c), correct), None

    xs = (
        jnp.moveaxis(h.reshape(B, nc, Cs, d), 1, 0),
        jnp.moveaxis(batch["targets"].reshape(B, nc, Cs), 1, 0),
        jnp.moveaxis(mask.reshape(B, nc, Cs), 1, 0),
    )
    (loss_sum, n_tok, correct), _ = lax.scan(
        jax.checkpoint(chunk_fn),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
         jnp.zeros((), jnp.float32)),
        xs,
    )
    denom = jnp.maximum(n_tok, 1.0)
    ce = loss_sum / denom
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux, "accuracy": correct / denom,
                  "tokens": n_tok}


# ---------------------------------------------------------------------------
# serving entry points
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params, tokens, caches, *, mrope_positions=None,
            patch_embeds=None, constrain=None):
    """Teacher-forced pass that fills caches; returns last-position logits."""
    h, new_caches, _ = forward(cfg, params, tokens, caches=caches,
                               cache_pos=None, mrope_positions=mrope_positions,
                               patch_embeds=patch_embeds, constrain=constrain)
    return logits_at(cfg, params, h[:, -1]), new_caches


def decode_step(cfg: ModelConfig, params, token, caches, cache_pos, *,
                mrope_positions=None, constrain=None):
    """token: (B,1); cache_pos: (B,) current position. Returns (B,V) logits."""
    h, new_caches, _ = forward(cfg, params, token, caches=caches,
                               cache_pos=cache_pos,
                               mrope_positions=mrope_positions,
                               constrain=constrain)
    return logits_at(cfg, params, h[:, -1]), new_caches
