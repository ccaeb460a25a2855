"""Dense gated FFN (SwiGLU / GeGLU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import sctx
from repro.models.common import ModelConfig, ParamDef, act_fn


def ffn_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": ParamDef((d, f), ("embed", "ff")),
        "w_up": ParamDef((d, f), ("embed", "ff")),
        "w_down": ParamDef((f, d), ("ff", "embed_out")),
    }


@jax.named_scope("mlp")
def ffn_block(cfg: ModelConfig, p, x):
    cd = cfg.compute_dtype
    act = act_fn(cfg.act)
    g = act(sctx.shard(jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cd)),
                       "batch", "seq", "ff"))
    u = sctx.shard(jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cd)),
                   "batch", "seq", "ff")
    return sctx.shard(
        jnp.einsum("bsf,fd->bsd", g * u, p["w_down"].astype(cd)),
        "batch", "seq", "embed")
