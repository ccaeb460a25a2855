"""Attention: blocked (flash-style) pure-JAX attention + RoPE/M-RoPE + GQA
+ sliding-window + decode-with-cache.

The blocked implementation is the production CPU/dry-run path AND the oracle
for the Pallas kernel (kernels/flash_attention.py). It never materializes the
full (Sq × Skv) score matrix: an outer scan over query blocks and an inner
online-softmax scan over KV blocks keep the working set at
(q_block × kv_block) per head — the same tiling the TPU kernel uses in VMEM.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import sctx
from repro.models.common import ModelConfig, ParamDef, rms_norm, softcap

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def _rope_inv_freq(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    half = x.shape[-1] // 2
    inv = _rope_inv_freq(x.shape[-1], theta)
    ang = positions[..., None].astype(jnp.float32) * inv      # (..., S, half)
    sin = jnp.sin(ang)[..., None, :]                          # (..., S, 1, half)
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x32_1 * cos - x32_2 * sin, x32_2 * cos + x32_1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def apply_mrope(x, positions, theta: float, sections):
    """Qwen2-VL multimodal RoPE. positions: (3, ..., S) for (t, h, w);
    ``sections`` splits the rotary half-dim across the three streams."""
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    inv = _rope_inv_freq(x.shape[-1], theta)                  # (half,)
    # pick, per rotary channel, which position stream drives it
    sec_id = jnp.repeat(
        jnp.arange(3), jnp.array(sections), total_repeat_length=half
    )                                                          # (half,)
    # positions: (3, ..., S) -> (..., S, half) by selecting stream per channel
    pos = jnp.moveaxis(positions[sec_id], 0, -1)               # (..., S, half)
    ang = pos.astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x32_1 * cos - x32_2 * sin, x32_2 * cos + x32_1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blocked flash-style attention (training / prefill)
#
# Two paths:
#  * autodiff path (kv_valid_len / softcap support) — serving only;
#  * custom-VJP path (training default): the backward recomputes score
#    tiles from (q, k, v, out, lse) — flash-attention backward — instead of
#    saving the online-softmax carries of every KV step, which costs
#    O(S·D·n_kv_blocks) residual memory under scan autodiff.
# ---------------------------------------------------------------------------

def _tile_mask(q_pos, kv_pos, causal: bool, window: int):
    """(qb, kb) boolean mask tile from absolute positions."""
    m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= kv_pos[None, :]
    if window:
        m &= q_pos[:, None] - kv_pos[None, :] < window
    return m


def blocked_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                      kv_valid_len=None, q_block=512, kv_block=1024,
                      cap=0.0):
    """Online-softmax attention without materializing (Sq × Skv).

    q: (B, Sq, H, D); k, v: (B, Skv, KVH, D) with H % KVH == 0.
    window: 0 = none, else sliding window (local attention).
    q_offset: absolute position of q[0] (prefill continuation / decode).
    kv_valid_len: mask kv positions >= this (cache not yet filled).

    Inputs keep their (bf16) dtype — scores/accumulators are fp32 via MXU
    native mixed precision (preferred_element_type), which halves the
    activation footprint vs upcasting q/k/v.
    """
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    Dv = v.shape[-1]                      # may differ from D (MLA)
    G = H // KVH
    scale = 1.0 / math.sqrt(D)

    qb = min(q_block, Sq)
    kb = min(kv_block, Skv)
    # pad to block multiples
    pq, pk = (-Sq) % qb, (-Skv) % kb
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (Sq + pq) // qb, (Skv + pk) // kb

    out_dtype = q.dtype
    q = q.reshape(B, nq, qb, KVH, G, D)
    k = k.reshape(B, nk, kb, KVH, D)
    v = v.reshape(B, nk, kb, KVH, Dv)

    q_pos = q_offset + jnp.arange(Sq + pq).reshape(nq, qb)
    kv_pos = jnp.arange(Skv + pk).reshape(nk, kb)
    kv_lim = Skv if kv_valid_len is None else kv_valid_len

    def q_block_fn(qpos_tile, q_tile):
        # q_tile: (B, qb, KVH, G, D); qpos_tile: (qb,)
        def kv_step(carry, inputs):
            m_run, l_run, acc = carry
            k_tile, v_tile, kpos = inputs           # (B,kb,KVH,D), (kb,)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_tile, k_tile,
                           preferred_element_type=jnp.float32) * scale
            s = softcap(s, cap)
            mask = _tile_mask(qpos_tile, kpos, causal, window)
            mask &= (kpos < kv_lim)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_tile.dtype), v_tile,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, KVH, G, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, qb), jnp.float32)
        a0 = jnp.zeros((B, KVH, G, qb, Dv), jnp.float32)
        (m, l, acc), _ = lax.scan(
            kv_step, (m0, l0, a0),
            (k.swapaxes(0, 1), v.swapaxes(0, 1), kv_pos),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(out_dtype)                 # (B, KVH, G, qb, Dv)

    # outer scan over q blocks keeps the HLO size O(1) in sequence length
    _, out = lax.scan(
        lambda _, inp: (0, jax.checkpoint(q_block_fn)(inp[0], inp[1])),
        0, (q_pos, q.swapaxes(0, 1)),
    )                                                # (nq, B, KVH, G, qb, Dv)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq + pq, H, Dv)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# custom-VJP flash path (training)
# ---------------------------------------------------------------------------

def _flash_fwd_impl(q, k, v, causal, window, qb, kb):
    """Returns out (B,Sq,H,Dv) and lse (B,Sq,H) fp32."""
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    nq, nk = Sq // qb, Skv // kb
    qr = q.reshape(B, nq, qb, KVH, G, D).swapaxes(0, 1)
    kr = k.reshape(B, nk, kb, KVH, D).swapaxes(0, 1)
    vr = v.reshape(B, nk, kb, KVH, Dv).swapaxes(0, 1)
    q_pos = jnp.arange(Sq).reshape(nq, qb)
    kv_pos = jnp.arange(Skv).reshape(nk, kb)

    def q_block(qpos_tile, q_tile):
        def kv_step(carry, inputs):
            m_run, l_run, acc = carry
            k_tile, v_tile, kpos = inputs
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_tile, k_tile,
                           preferred_element_type=jnp.float32) * scale
            mask = _tile_mask(qpos_tile, kpos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_tile.dtype), v_tile,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, KVH, G, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, qb), jnp.float32)
        a0 = jnp.zeros((B, KVH, G, qb, Dv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), (kr, vr, kv_pos))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return out, lse                              # (B,KVH,G,qb,·)

    _, (out, lse) = lax.scan(
        lambda _, inp: (0, jax.checkpoint(q_block)(inp[0], inp[1])),
        0, (q_pos, qr))
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, Dv)
    lse = lse.transpose(1, 0, 4, 2, 3).reshape(B, Sq, H)
    return out, lse


def _flash_bwd_impl(q, k, v, out, lse, dout, causal, window, qb, kb):
    """Flash backward: recompute p = exp(s − lse) per tile; never saves the
    online-softmax carries. dk/dv accumulate in fp32 over the q-block scan."""
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    nq, nk = Sq // qb, Skv // kb

    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                          # (B,Sq,H)
    r5 = lambda t, n, b_: t.reshape(B, n, b_, KVH, G, -1).swapaxes(0, 1)
    qr = r5(q, nq, qb)
    dor = r5(dout, nq, qb)
    lser = lse.reshape(B, nq, qb, KVH, G).swapaxes(0, 1)
    deltar = delta.reshape(B, nq, qb, KVH, G).swapaxes(0, 1)
    kr = k.reshape(B, nk, kb, KVH, D)
    vr = v.reshape(B, nk, kb, KVH, Dv)
    q_pos = jnp.arange(Sq).reshape(nq, qb)
    kv_pos = jnp.arange(Skv).reshape(nk, kb)

    def q_step(carry, inp):
        dk_acc, dv_acc = carry                       # fp32 (B,nk,kb,KVH,·)
        q_i, do_i, lse_i, delta_i, qpos_i = inp

        def kv_step(dq_i, j):
            k_j = kr[:, j]
            v_j = vr[:, j]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = _tile_mask(qpos_i, kv_pos[j], causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lse_i.transpose(0, 2, 3, 1)[..., None])
            dv_j = jnp.einsum("bhgqk,bqhgv->bkhv", p.astype(do_i.dtype),
                              do_i, preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqhgv,bkhv->bhgqk", do_i, v_j,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta_i.transpose(0, 2, 3, 1)[..., None]) * scale
            dq_i = dq_i + jnp.einsum("bhgqk,bkhd->bqhgd",
                                     ds.astype(k_j.dtype), k_j,
                                     preferred_element_type=jnp.float32)
            dk_j = jnp.einsum("bhgqk,bqhgd->bkhd", ds.astype(q_i.dtype),
                              q_i, preferred_element_type=jnp.float32)
            return dq_i, (dk_j, dv_j)

        dq0 = jnp.zeros((B, qb, KVH, G, D), jnp.float32)
        dq_i, (dk_js, dv_js) = lax.scan(kv_step, dq0, jnp.arange(nk))
        # dk_js: (nk, B, kb, KVH, D) — add into the accumulators
        dk_acc = dk_acc + dk_js.swapaxes(0, 1)
        dv_acc = dv_acc + dv_js.swapaxes(0, 1)
        return (dk_acc, dv_acc), dq_i

    dk0 = jnp.zeros((B, nk, kb, KVH, D), jnp.float32)
    dv0 = jnp.zeros((B, nk, kb, KVH, Dv), jnp.float32)
    (dk, dv), dq = lax.scan(
        lambda c, inp: jax.checkpoint(q_step)(c, inp),
        (dk0, dv0), (qr, dor, lser, deltar, q_pos))

    dq = dq.swapaxes(0, 1).reshape(B, Sq, H, D).astype(q.dtype)
    dk = dk.reshape(B, Skv, KVH, D).astype(k.dtype)
    dv = dv.reshape(B, Skv, KVH, Dv).astype(v.dtype)
    return dq, dk, dv


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, window, qb, kb):
    out, _ = _flash_fwd_impl(q, k, v, causal, window, qb, kb)
    return out


def _flash_vjp_fwd(q, k, v, causal, window, qb, kb):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, qb, kb)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, window, qb, kb, res, dout):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, dout, causal, window, qb, kb)


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_train(q, k, v, *, causal=True, window=0, q_block=512,
                          kv_block=1024):
    """Training-path attention with the manual flash backward. Pads to
    block multiples; no kv_valid_len/softcap (serving uses the autodiff
    path)."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    qb = min(q_block, Sq)
    kb = min(kv_block, Skv)
    pq, pk = (-Sq) % qb, (-Skv) % kb
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        if not causal:
            # padded KV columns must be masked out; causal+window masks
            # already exclude them for q < Sq, but pure full attention
            # needs the length mask — fall back to the autodiff path.
            raise ValueError("flash_attention_train requires causal=True "
                             "when padding KV")
    out = _flash_attention(q, k, v, causal, window, qb, kb)
    return out[:, :Sq]


def decode_attention(q, k_cache, v_cache, valid_mask, cap=0.0):
    """Single-position attention vs a cache.

    q: (B, 1, H, D); k_cache/v_cache: (B, S, KVH, D);
    valid_mask: (B, S) or (S,) bool — which cache slots participate.
    O(S) per new token; the cache's S dim may be sharded (GSPMD reduces
    the partial softmax terms — flash-decoding style).
    """
    B, _, H, D = q.shape
    KVH = k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = H // KVH
    scale = 1.0 / math.sqrt(D)
    # keep the (large, sharded) cache in its storage dtype; accumulate the
    # contractions in fp32 on the MXU instead of materializing an fp32 copy
    qg = q.reshape(B, KVH, G, D).astype(k_cache.dtype)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap)
    if valid_mask.ndim == 1:
        valid_mask = valid_mask[None]
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dv)


# ---------------------------------------------------------------------------
# the attention block (params + forward)
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig) -> dict:
    D = cfg.resolved_head_dim
    d = cfg.d_model
    H, KVH = cfg.n_heads, cfg.n_kv_heads
    defs = {
        "wq": ParamDef((d, H, D), ("embed", "q_heads", "head_dim")),
        "wk": ParamDef((d, KVH, D), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, KVH, D), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((H, D, d), ("q_heads", "head_dim", "embed_out")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H, D), ("q_heads", "head_dim"), init="zeros")
        defs["bk"] = ParamDef((KVH, D), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = ParamDef((KVH, D), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((D,), ("head_dim",), init="zeros")
        defs["k_norm"] = ParamDef((D,), ("head_dim",), init="zeros")
    return defs


def _project_qkv(cfg: ModelConfig, p, x, positions, *, theta,
                 mrope_positions=None):
    cd = cfg.compute_dtype
    q = sctx.shard(jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd)),
                   "batch", "seq", "heads", "head_dim")
    k = sctx.shard(jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cd)),
                   "batch", "seq", "kv_heads", "head_dim")
    v = sctx.shard(jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cd)),
                   "batch", "seq", "kv_heads", "head_dim")
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.mrope_sections is not None and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_positions, theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


@jax.named_scope("attention")
def attention_block(cfg: ModelConfig, p, x, positions, *, kind="attn",
                    cache=None, cache_pos=None, mrope_positions=None):
    """One attention block.

    Modes:
      * cache is None              — training / teacher-forced forward.
      * cache given, x.shape[1]==1 — decode: read+update cache at cache_pos.
      * cache given, x longer      — prefill: fill cache, return outputs.

    cache: dict(k=(B,Sc,KVH,D), v=..., offset=()) — for "local" layers Sc is
    the ring-buffer window; for "attn" (global) layers Sc is the max context.
    """
    cd = cfg.compute_dtype
    window = cfg.window if kind == "local" else 0
    theta = cfg.rope_theta if kind == "local" or not cfg.rope_theta_global \
        else cfg.rope_theta_global
    q, k, v = _project_qkv(cfg, p, x, positions, theta=theta,
                           mrope_positions=mrope_positions)

    new_cache = cache
    if cache is None:
        out = flash_attention_train(q, k, v, causal=True, window=window,
                                    q_block=cfg.attn_q_block,
                                    kv_block=cfg.attn_kv_block)
    elif x.shape[1] == 1:
        Sc = cache["k"].shape[1]
        if window:
            slot = (cache_pos % Sc)[..., None]
        else:
            slot = cache_pos[..., None]
        bidx = jnp.arange(x.shape[0])[:, None]
        k_c = cache["k"].at[bidx, slot].set(k.astype(cache["k"].dtype))
        v_c = cache["v"].at[bidx, slot].set(v.astype(cache["v"].dtype))
        slots = jnp.arange(Sc)
        if window:
            # ring buffer: before wrap-around only slots 0..pos are written;
            # after wrap-around every slot holds one of the last Sc tokens.
            valid = (slots[None, :] <= cache_pos[:, None]) | \
                    (cache_pos[:, None] >= Sc)
        else:
            valid = slots[None, :] <= cache_pos[:, None]
        out = decode_attention(q, k_c.astype(cd), v_c.astype(cd), valid,
                               cap=0.0)
        new_cache = {"k": k_c, "v": v_c}
    else:
        out = blocked_attention(q, k, v, causal=True, window=window)
        Sc = cache["k"].shape[1]
        S = x.shape[1]
        if S >= Sc:
            k_w, v_w = k[:, -Sc:], v[:, -Sc:]
            k_c = k_w.astype(cache["k"].dtype)
            v_c = v_w.astype(cache["v"].dtype)
            if window and Sc:
                # keep ring-buffer slot alignment: roll so that token t sits
                # at slot t % Sc
                shift = S % Sc
                k_c = jnp.roll(k_c, shift, axis=1)
                v_c = jnp.roll(v_c, shift, axis=1)
        else:
            k_c = cache["k"].at[:, :S].set(k.astype(cache["k"].dtype))
            v_c = cache["v"].at[:, :S].set(v.astype(cache["v"].dtype))
        new_cache = {"k": k_c, "v": v_c}

    out = sctx.shard(out.astype(cd), "batch", "seq", "heads", "head_dim")
    y = sctx.shard(jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd)),
                   "batch", "seq", "embed")
    return y, new_cache
