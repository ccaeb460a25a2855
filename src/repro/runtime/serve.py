"""Serving-step builders: sharded prefill and decode, plus a simple
continuous-batching engine used by examples/serve.py.

Dry-run shapes: ``prefill_32k`` lowers the prefill step (B=32, S=32768);
``decode_32k`` / ``long_500k`` lower ONE decode step against a KV cache of
the given length (the assignment's definition of the decode cells).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import sctx
from repro.models import transformer as tfm
from repro.models.common import ModelConfig, abstract_params
from repro.runtime import sharding as shd


@dataclasses.dataclass(frozen=True)
class ServeBuild:
    prefill: Any              # (params, tokens, [extras]) -> (logits, caches)
    decode: Any               # (params, caches, token, pos) -> (logits, caches)
    abstract_params: Any
    abstract_caches: Any
    param_specs: Any
    cache_spec_tree: Any
    token_spec: Any


def _extra_kwargs(cfg, B, S):
    sd = jax.ShapeDtypeStruct
    extras = {}
    if cfg.mrope_sections is not None:
        extras["mrope_positions"] = sd((3, B, S), jnp.int32)
    if cfg.patch_embed_tokens and S > cfg.patch_embed_tokens:
        extras["patch_embeds"] = sd((B, cfg.patch_embed_tokens, cfg.d_model),
                                    cfg.compute_dtype)
    return extras


def build_serve_steps(cfg: ModelConfig, mesh, *, batch: int, max_len: int):
    pspecs = shd.param_specs(cfg, mesh)
    cspecs = shd.cache_specs(cfg, mesh, batch, max_len)
    tok_spec = shd.serve_token_specs(cfg, mesh, batch)
    named = lambda t: shd.named(mesh, t)
    constrain = shd.block_constrainer(cfg, mesh)

    act_spec = shd.activation_spec(cfg, mesh)

    def _extra_specs(S):
        b_ax = tok_spec[0]
        specs = {}
        if cfg.mrope_sections is not None:
            specs["mrope_positions"] = P(None, b_ax, None)
        if cfg.patch_embed_tokens and S > cfg.patch_embed_tokens:
            specs["patch_embeds"] = P(b_ax, None, None)
        return specs

    # modality extras travel as a positional dict (jit with in_shardings
    # does not accept kwargs)
    def prefill_fn(params, tokens, extras):
        caches = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            tfm.init_cache_defs(cfg, batch, max_len))
        caches = jax.lax.with_sharding_constraint(caches, named(cspecs))
        with sctx.use(mesh, act_spec):
            return tfm.prefill(cfg, params, tokens, caches,
                               constrain=constrain, **extras)

    def decode_fn(params, caches, token, pos, extras):
        with sctx.use(mesh, act_spec):
            return tfm.decode_step(cfg, params, token, caches, pos,
                                   constrain=constrain, **extras)

    jit_prefill = jax.jit(
        prefill_fn,
        in_shardings=(named(pspecs), named(tok_spec),
                      named(_extra_specs(max_len))),
        out_shardings=(None, named(cspecs)),
    )
    jit_decode = jax.jit(
        decode_fn,
        in_shardings=(named(pspecs), named(cspecs), named(tok_spec),
                      named(P("data" if tok_spec == P("data", None) else None)),
                      named(_extra_specs(1))),
        out_shardings=(None, named(cspecs)),
        donate_argnums=(1,),
    )
    return ServeBuild(
        prefill=jit_prefill,
        decode=jit_decode,
        abstract_params=abstract_params(tfm.model_defs(cfg), cfg.param_dtype),
        abstract_caches=tfm.init_cache_defs(cfg, batch, max_len),
        param_specs=pspecs,
        cache_spec_tree=cspecs,
        token_spec=tok_spec,
    )


# ---------------------------------------------------------------------------
# minimal continuous-batching engine (examples/serve.py)
# ---------------------------------------------------------------------------

class BatchingEngine:
    """Greedy decode over a fixed batch of request slots.

    Requests join free slots; each step decodes one token for every active
    slot; finished requests free their slot. Small-model CPU demo of the
    serving path (the same jitted decode step the dry-run lowers).
    """

    def __init__(self, cfg: ModelConfig, params, batch: int, max_len: int):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.caches = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            tfm.init_cache_defs(cfg, batch, max_len))
        self.pos = jnp.zeros((batch,), jnp.int32)
        self.cur = jnp.zeros((batch, 1), jnp.int32)
        self.active = [False] * batch
        self.outputs: dict[int, list] = {}
        self._decode = jax.jit(
            lambda p, c, t, pos: tfm.decode_step(cfg, p, t, c, pos))
        self._next_id = 0

    def submit(self, prompt_tokens) -> int | None:
        """Prefill a single request into a free slot; returns request id."""
        try:
            slot = self.active.index(False)
        except ValueError:
            return None
        rid = self._next_id
        self._next_id += 1
        # single-request prefill (slot-wise): decode tokens one by one to
        # fill this slot's cache without disturbing others.
        for t, tok in enumerate(prompt_tokens):
            tok_arr = self.cur.at[slot, 0].set(int(tok))
            pos_arr = self.pos.at[slot].set(t)
            logits, self.caches = self._decode(self.params, self.caches,
                                               tok_arr, pos_arr)
        self.pos = self.pos.at[slot].set(len(prompt_tokens))
        nxt = int(jnp.argmax(logits[slot]))
        self.cur = self.cur.at[slot, 0].set(nxt)
        self.active[slot] = True
        self.outputs[rid] = [nxt]
        self._slot_of = getattr(self, "_slot_of", {})
        self._slot_of[rid] = slot
        return rid

    def step(self, stop_len: int = 16):
        logits, self.caches = self._decode(self.params, self.caches,
                                           self.cur, self.pos)
        nxt = jnp.argmax(logits, axis=-1)
        self.cur = nxt[:, None].astype(jnp.int32)
        self.pos = self.pos + jnp.asarray(
            [1 if a else 0 for a in self.active], jnp.int32)
        done = []
        for rid, slot in list(getattr(self, "_slot_of", {}).items()):
            if not self.active[slot]:
                continue
            self.outputs[rid].append(int(nxt[slot]))
            if len(self.outputs[rid]) >= stop_len or \
                    int(self.pos[slot]) >= self.max_len - 1:
                self.active[slot] = False
                done.append(rid)
                del self._slot_of[rid]
        return done
