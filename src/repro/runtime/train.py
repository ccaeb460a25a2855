"""Train-step builder: model fwd/bwd (per pod, vmapped) + Sync-EASGD
exchange (core.elastic) under one jit.

The step is the paper's Algorithm 4 adapted to the pod mesh:
  1. each pod computes grads on its own batch shard (intra-pod DP over
     `data` via GSPMD — the paper's within-node sync step);
  2. the ONE packed cross-pod collective exchanges start-of-step weights
     (overlappable with (1) — Sync EASGD3);
  3. fused elementwise EASGD update (eqs. 5–6 + 2).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import elastic
from repro.core.elastic import ElasticConfig, ElasticState
from repro.models import sctx
from repro.models import transformer as tfm
from repro.models.common import ModelConfig, abstract_params, init_params
from repro.runtime import sharding as shd


@dataclasses.dataclass(frozen=True)
class TrainBuild:
    """Everything the launcher / dry-run needs for one training setup."""
    step: Any                 # jitted (state, batch) -> (state, metrics)
    state_specs: Any          # ElasticState PartitionSpecs
    batch_spec_tree: Any      # batch PartitionSpecs
    abstract_state: Any       # ShapeDtypeStruct ElasticState
    init_state: Any           # jitted () -> ElasticState, sharded like step
    param_specs: Any
    n_pods: int
    exchange_plan: Any = None  # repro.comm.ExchangePlan the step executes


def _per_pod_loss(cfg: ModelConfig, constrain=None):
    def loss(params, batch):
        return tfm.lm_loss(cfg, params, batch,
                           extra_fwd_kwargs={"constrain": constrain})
    return loss


def make_batch_defs(cfg: ModelConfig, n_pods: int, per_pod_batch: int,
                    seq: int):
    """Abstract training batch with leading (n_pods, B_local, S) layout."""
    B, S = per_pod_batch, seq
    sd = jax.ShapeDtypeStruct
    batch = {
        "tokens": sd((n_pods, B, S), jnp.int32),
        "targets": sd((n_pods, B, S), jnp.int32),
        "mask": sd((n_pods, B, S), jnp.float32),
    }
    if cfg.mrope_sections is not None:
        batch["mrope_positions"] = sd((n_pods, 3, B, S), jnp.int32)
    if cfg.patch_embed_tokens:
        batch["patch_embeds"] = sd(
            (n_pods, B, cfg.patch_embed_tokens, cfg.d_model),
            cfg.compute_dtype)
    return batch


def build_train_step(cfg: ModelConfig, ecfg: ElasticConfig, mesh,
                     *, n_pods: int, per_pod_batch: int, seq: int,
                     seed: int = 0, microbatches: int = 1) -> TrainBuild:
    """``microbatches`` > 1 scans gradient accumulation over batch slices —
    activation memory scales with the microbatch while the optimizer step
    (and the cross-pod exchange) still sees the full global batch. Same
    math: grads are means over the full batch either way."""
    pspecs = shd.param_specs(cfg, mesh)
    pod_axis = "pod" if "pod" in mesh.axis_names else None
    sspecs = elastic.state_specs(pspecs, ecfg, pod_axis)
    defs = tfm.model_defs(cfg)
    abstract_p = abstract_params(defs, cfg.param_dtype)
    n_param_elems = sum(
        l.size for l in jax.tree_util.tree_leaves(abstract_p))
    # the ONE cross-pod exchange (schedule × packing × compression ×
    # overlap), built once and executed by every step; "auto" resolves here
    # from the packed wire bytes and pod count
    exchange_plan = ecfg.exchange_plan(
        axis_name=pod_axis if (n_pods > 1 and pod_axis is not None) else None,
        n_total=n_pods, n_elements=n_param_elems)
    bspecs = shd.batch_specs(cfg, mesh, pod_dim=pod_axis is not None)
    assert per_pod_batch % microbatches == 0, (per_pod_batch, microbatches)

    loss_fn = _per_pod_loss(cfg, shd.block_constrainer(cfg, mesh))
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    vmap_kw = {"spmd_axis_name": pod_axis} if pod_axis else {}
    act_spec = shd.activation_spec(cfg, mesh)

    def grads_of(params_pod, batch):
        with sctx.use(mesh, act_spec):
            (loss, metrics), grads = jax.vmap(grad_fn, **vmap_kw)(
                params_pod, batch)
        return loss, metrics, grads

    def sync_easgd_step(state: ElasticState, batch):
        # per-pod fwd/bwd; intra-pod data-parallel reduction happens via the
        # batch's `data` sharding (GSPMD inserts the gradient all-reduce).
        if microbatches == 1:
            loss, metrics, grads = grads_of(state.params, batch)
        else:
            # batch leaves: (n_pods, B, ...) -> (m, n_pods, B/m, ...);
            # mrope_positions carries batch at axis 2: (n_pods, 3, B, S)
            def split(x, axis):
                shape = (x.shape[:axis] + (microbatches, -1)
                         + x.shape[axis + 1:])
                return jnp.moveaxis(x.reshape(shape), axis, 0)
            micro = {
                k: split(v, 2 if k == "mrope_positions" else 1)
                for k, v in batch.items()
            }

            def acc_fn(carry, mb):
                g_acc, loss_acc, m_acc = carry
                loss, metrics, grads = grads_of(state.params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype) / microbatches,
                    g_acc, grads)
                m_acc = jax.tree_util.tree_map(
                    lambda a, m: a + m / microbatches, m_acc, metrics)
                return (g_acc, loss_acc + loss / microbatches, m_acc), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            zero_metrics = {
                "ce": jnp.zeros((n_pods,)), "aux": jnp.zeros((n_pods,)),
                "accuracy": jnp.zeros((n_pods,)),
                "tokens": jnp.zeros((n_pods,)),
            }
            (grads, loss, metrics), _ = jax.lax.scan(
                acc_fn, (g0, jnp.zeros((n_pods,)), zero_metrics), micro)
        new_state = elastic.apply_gradients(
            state, grads, ecfg, mesh=mesh, param_specs=pspecs,
            pod_axis=pod_axis, plan=exchange_plan)
        out_metrics = {
            "loss": jnp.mean(loss),
            **{k: jnp.mean(v) for k, v in metrics.items()},
        }
        return new_state, out_metrics

    abstract_state = elastic.init_abstract(abstract_p, ecfg, n_pods)

    # built on the mesh with the step's own shardings: each device allocates
    # only its share, and step 0 sees the layout every later step returns
    # (an eager init leaves unsharded arrays, so step 1 would compile again)
    @partial(jax.jit, out_shardings=shd.named(mesh, sspecs))
    def init_state():
        params = init_params(defs, jax.random.PRNGKey(seed), cfg.param_dtype)
        return elastic.init(params, ecfg, n_pods)

    jit_step = jax.jit(
        sync_easgd_step,
        in_shardings=(shd.named(mesh, sspecs), shd.named(mesh, bspecs)),
        out_shardings=(shd.named(mesh, sspecs), None),
        donate_argnums=(0,),
    )
    return TrainBuild(
        step=jit_step, state_specs=sspecs, batch_spec_tree=bspecs,
        abstract_state=abstract_state, init_state=init_state,
        param_specs=pspecs, n_pods=n_pods, exchange_plan=exchange_plan,
    )
