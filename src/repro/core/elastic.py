"""Multi-pod Sync EASGD — the paper's technique as a first-class JAX module.

Mapping (DESIGN.md §2): each **pod** is one EASGD worker. Inside a pod the
gradient is reduced synchronously over the `data` axis (fast ICI — GSPMD does
this automatically from the batch sharding). Across pods, workers exchange
*weights, not gradients*, every ``tau`` steps through the elastic-averaging
rules (paper eqs. 1–2 / 5–6), using the paper's three co-design techniques:

 1. **Packed single-buffer exchange** (paper §5.2): inside ``shard_map`` each
    device flattens its *local shards* of every parameter into one contiguous
    buffer and issues a SINGLE cross-pod all-reduce. Packing in shard-space
    is a pure local reshape — no resharding traffic — while guaranteeing one
    collective (one α) instead of one per tensor.
 2. **Device-resident weights** (paper §6.1.2): all state lives in HBM; the
    step never round-trips the host.
 3. **Compute/communication overlap** (paper §6.1.3): the exchange reads only
    the *start-of-step* weights W_t — by construction it has no data
    dependency on the current forward/backward, so XLA's latency-hiding
    scheduler overlaps the cross-pod collective with compute.
    ``overlap=False`` inserts an optimization barrier to reproduce the
    non-overlapped baseline (Sync EASGD1/2).

Representation: every worker-local tensor carries a leading ``pod`` dimension
of size ``n_pods`` sharded on the mesh's ``pod`` axis (size 1 and unsharded
on a single-pod mesh — same code path). The center weight W̄ has no pod dim
(replicated across pods, sharded over data/model like the params).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.comm import plan as comm_plan
from repro.comm import schedules as comm_schedules
from repro.core import compression as compression_lib
from repro.core import costmodel
from repro.core.easgd import EASGDConfig
from repro.utils.pytree import tree_map


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    easgd: EASGDConfig = EASGDConfig()
    mode: str = "sync_easgd"        # "sync_easgd" | "msgd" (plain DP baseline)
    packed: bool = True             # paper §5.2: single-buffer exchange
    schedule: str = "psum"          # repro.comm schedule for the packed
    #                                 cross-pod collective (paper §5.1/§6.1);
    #                                 "auto" picks via comm.choose from the
    #                                 packed wire bytes + pod count at build
    compression: str = "none"       # none | bf16 | sign_ef (cross-pod only)
    overlap: bool = True            # paper §6.1.3 (Sync EASGD3)
    momentum_dtype: Any = jnp.float32
    center_dtype: Any = jnp.float32

    def __post_init__(self):
        assert self.mode in ("sync_easgd", "msgd"), self.mode
        if self.schedule != "auto":
            comm_schedules.get(self.schedule)   # validate
        compression_lib.get(self.compression)   # validate

    def resolve_schedule(self, n_total: int,
                         n_elements: int | None = None) -> str:
        """Resolve "auto" to a concrete registry name via ``comm.choose``
        on the POST-compression wire bytes over the cross-pod (DCI) link —
        the JIT accounting (sign_ef travels as int8 in the compiled
        collective), so the choice and the HLO report agree on bytes.
        Without a buffer size, fall back to psum (XLA-native)."""
        if self.schedule != "auto":
            return self.schedule
        if n_elements is None or n_total <= 1:
            return "psum"
        comp = compression_lib.get(self.compression)
        wire = n_elements * comp.jit_wire_bytes_per_element
        return comm_schedules.choose(wire, n_total, costmodel.TPU_DCI)

    def exchange_plan(self, axis_name: str | None, n_total: int,
                      n_elements: int | None = None
                      ) -> comm_plan.ExchangePlan:
        """The fully-composed cross-pod exchange this config describes.
        ``n_elements`` (packed fp32 buffer size) feeds the "auto" schedule
        choice; ignored for a concrete schedule name."""
        return comm_plan.make_plan(
            schedule=self.resolve_schedule(n_total, n_elements),
            compression=self.compression,
            overlap=self.overlap, axis_name=axis_name, n_total=n_total)


class ElasticState(NamedTuple):
    step: jnp.ndarray       # () int32
    params: Any             # pytree, leaves (n_pods, …) — local W⁽ⁱ⁾
    momentum: Any           # pytree, leaves (n_pods, …) — V⁽ⁱ⁾
    center: Any             # pytree, leaves (…) — W̄ (None for msgd)
    ef_error: Any           # pytree like params (compression only) or None


def n_pods_of(state: ElasticState) -> int:
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    return leaf.shape[0]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(params, cfg: ElasticConfig, n_pods: int = 1) -> ElasticState:
    """Broadcast a single parameter pytree into per-pod local weights
    (paper Alg. 4 lines 4–7: broadcast W, create local + global copies)."""
    pod = lambda x: jnp.broadcast_to(x[None], (n_pods,) + x.shape)
    params_pod = tree_map(pod, params)
    momentum = tree_map(
        lambda x: jnp.zeros((n_pods,) + x.shape, cfg.momentum_dtype), params
    )
    if cfg.mode == "msgd":
        center = None
    else:
        center = tree_map(lambda x: x.astype(cfg.center_dtype), params)
    if cfg.compression != "none" and cfg.mode != "msgd":
        ef = tree_map(
            lambda x: jnp.zeros((n_pods,) + x.shape, jnp.float32), params
        )
    else:
        ef = None
    return ElasticState(jnp.zeros((), jnp.int32), params_pod, momentum,
                        center, ef)


def init_abstract(params_abs, cfg: ElasticConfig, n_pods: int = 1):
    """ShapeDtypeStruct version of ``init`` (for the dry-run / AOT path)."""
    return jax.eval_shape(lambda p: init(p, cfg, n_pods), params_abs)


# ---------------------------------------------------------------------------
# state sharding specs
# ---------------------------------------------------------------------------

def state_specs(param_specs, cfg: ElasticConfig, pod_axis: str | None):
    """PartitionSpecs for an ElasticState given per-param specs (no pod dim).

    Local (per-pod) tensors get a leading pod-axis entry; the center is
    replicated across pods (no pod dim in its shape).
    """
    def podded(spec: P) -> P:
        return P(pod_axis, *spec)

    params = tree_map(podded, param_specs)
    center = None if cfg.mode == "msgd" else param_specs
    ef = params if (cfg.compression != "none" and cfg.mode != "msgd") else None
    return ElasticState(P(), params, params, center, ef)


# ---------------------------------------------------------------------------
# flat (packed) math — shared with kernels/ref and tests
# ---------------------------------------------------------------------------

def _pack_local(tree, pods: int | None = None):
    """Flatten a pytree of local shards into one contiguous fp32 buffer.

    Inside shard_map this is a per-device reshape+concat: zero communication.
    This IS the paper's 'single-layer layout' (§5.2) adapted to shard-space.
    With ``pods`` set, the leading pod dim stays OUTER: result (pods, n).
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if pods is None:
        return jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves]
        )
    return jnp.concatenate(
        [l.reshape(pods, -1).astype(jnp.float32) for l in leaves], axis=1
    )


def _unpack_local(buf, template, pods: int | None = None):
    leaves, treedef = jax.tree_util.tree_flatten(template)
    out, off = [], 0
    for l in leaves:
        if pods is None:
            size = l.size
            chunk = lax.slice_in_dim(buf, off, off + size)
        else:
            size = l.size // pods
            chunk = lax.slice_in_dim(buf, off, off + size, axis=1)
        out.append(chunk.reshape(l.shape).astype(l.dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# the update — one optimizer step given per-pod gradients
# ---------------------------------------------------------------------------

def _momentum_only(state: ElasticState, grads, cfg: ElasticConfig):
    """Between exchanges (step % τ ≠ 0) and for mode='msgd': eqs 3–4."""
    e = cfg.easgd
    v_new = tree_map(
        lambda v, g: (e.mu * v.astype(jnp.float32)
                      - e.eta * g.astype(jnp.float32)).astype(v.dtype),
        state.momentum, grads,
    )
    p_new = tree_map(
        lambda w, v: (w.astype(jnp.float32) + v.astype(jnp.float32)
                      ).astype(w.dtype),
        state.params, v_new,
    )
    return state._replace(step=state.step + 1, params=p_new, momentum=v_new)


def _elastic_tensors(state, grads, cfg, mean_w):
    """Per-tensor eqs 5–6 + eq 2 given the cross-pod mean of W_t."""
    e = cfg.easgd
    n_pods = n_pods_of(state)
    v_new = tree_map(
        lambda v, g: (e.mu * v.astype(jnp.float32)
                      - e.eta * g.astype(jnp.float32)).astype(v.dtype),
        state.momentum, grads,
    )
    p_new = tree_map(
        lambda w, v, c: (
            w.astype(jnp.float32) + v.astype(jnp.float32)
            - e.eta * e.rho * (w.astype(jnp.float32)
                               - c.astype(jnp.float32)[None])
        ).astype(w.dtype),
        state.params, v_new, state.center,
    )
    a = e.alpha * n_pods
    c_new = tree_map(
        lambda c, m: (c.astype(jnp.float32)
                      + a * (m.astype(jnp.float32) - c.astype(jnp.float32))
                      ).astype(c.dtype),
        state.center, mean_w,
    )
    return state._replace(step=state.step + 1, params=p_new, momentum=v_new,
                          center=c_new)


def _exchange_unpacked(state, grads, cfg):
    """Per-tensor cross-pod mean: one collective per parameter (the paper's
    'multiple rounds of communication for different layers' baseline).
    GSPMD may still combine small all-reduces; the packed path below makes
    the single message structural."""
    mean_w = tree_map(lambda w: jnp.mean(w.astype(jnp.float32), axis=0),
                      state.params)
    return _elastic_tensors(state, grads, cfg, mean_w)


def _exchange_packed(state, grads, cfg, mesh, param_specs, pod_axis,
                     plan=None):
    """Packed single-buffer exchange inside shard_map (paper §5.2 + §6.1).

    Every device: (a) locally flattens its shards of W_t into one buffer,
    (b) optionally compresses the delta vs W̄, (c) ONE collective over the
    pod axis using the plan's registered schedule (repro.comm — tree, ring,
    …), (d) fused elementwise update of W, V, W̄ (eqs 5–6, 2).
    """
    e = cfg.easgd
    n_pods = n_pods_of(state)
    pod_in_mesh = pod_axis is not None and pod_axis in mesh.axis_names
    if plan is None:
        n_elems = sum(l.size for l in
                      jax.tree_util.tree_leaves(state.params)) // n_pods
        plan = cfg.exchange_plan(
            axis_name=pod_axis if (n_pods > 1 and pod_in_mesh) else None,
            n_total=n_pods, n_elements=n_elems)

    specs = state_specs(param_specs, cfg,
                        pod_axis if (n_pods > 1 and pod_in_mesh) else None)
    grads_spec = specs.params
    out_specs = ElasticState(
        step=P(), params=specs.params, momentum=specs.momentum,
        center=specs.center, ef_error=specs.ef_error,
    )

    def body(step, params, momentum, center, ef, g):
        # local shards; pod-dim is size n_pods/|pod axis| locally (=1 on the
        # production mesh). The pod dim stays outer in the packed buffers.
        # The four phases carry named scopes (elastic.pack, .exchange,
        # .update, .unpack), so a profiler trace can split the update's
        # flat copies from its arithmetic.
        local_pods = jax.tree_util.tree_leaves(params)[0].shape[0]
        with jax.named_scope("elastic.pack"):
            w2 = _pack_local(params, local_pods)  # (local_pods, n_local)
            g2 = _pack_local(g, local_pods)
            v2 = _pack_local(momentum, local_pods)
            c2 = _pack_local(center)[None]        # (1, n_local)

        # --- the ONE cross-pod collective (plan = schedule × compression) --
        with jax.named_scope("elastic.exchange"):
            delta = (w2 - c2)
            if cfg.compression != "none":
                ef_flat = _pack_local(ef, local_pods)
                mean_delta, ef_new2 = plan.reduce_mean_flat(delta, ef_flat)
                ef_new = _unpack_local(ef_new2, ef, local_pods)
            else:
                mean_delta, _ = plan.reduce_mean_flat(delta)
                ef_new = ef
            mean_w = c2[0] + mean_delta

        # --- fused elementwise update (eqs 5–6 + 2) ------------------------
        with jax.named_scope("elastic.update"):
            v_new = e.mu * v2 - e.eta * g2
            w_new = w2 + v_new - e.eta * e.rho * (w2 - c2)
            c_new = c2[0] + e.alpha * n_pods * (mean_w - c2[0])

        with jax.named_scope("elastic.unpack"):
            return (
                step + 1,
                _unpack_local(w_new, params, local_pods),
                _unpack_local(v_new, momentum, local_pods),
                _unpack_local(c_new, center),
                ef_new,
            )

    in_specs = (P(), specs.params, specs.momentum, specs.center,
                specs.ef_error if cfg.compression != "none" else P(),
                grads_spec)
    shmapped = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), out_specs.params, out_specs.momentum,
                   out_specs.center,
                   out_specs.ef_error if cfg.compression != "none" else P()),
        check_vma=False,
    )
    ef_in = state.ef_error if cfg.compression != "none" else jnp.zeros((), jnp.float32)
    step, p_new, v_new, c_new, ef_new = shmapped(
        state.step, state.params, state.momentum, state.center, ef_in, grads
    )
    if cfg.compression == "none":
        ef_new = state.ef_error
    return ElasticState(step, p_new, v_new, c_new, ef_new)


def apply_gradients(state: ElasticState, grads, cfg: ElasticConfig,
                    mesh=None, param_specs=None,
                    pod_axis: str | None = "pod",
                    plan=None) -> ElasticState:
    """One optimizer step. ``grads`` is a pytree like ``state.params``
    (leading pod dim), already mean-reduced over the intra-pod data axis
    (GSPMD does that from the batch sharding). ``plan`` (an
    ``repro.comm.ExchangePlan``) overrides the exchange composition derived
    from ``cfg`` — the runtime builds it once per train-step.
    """
    if cfg.mode == "msgd":
        # plain synchronous momentum SGD: grads are averaged over pods too,
        # so all pods stay identical (pure DP baseline).
        n_pods = n_pods_of(state)
        if n_pods > 1:
            gmean = tree_map(
                lambda g: jnp.broadcast_to(
                    jnp.mean(g.astype(jnp.float32), axis=0, keepdims=True),
                    g.shape).astype(g.dtype),
                grads,
            )
        else:
            gmean = grads
        return _momentum_only(state, gmean, cfg)

    if not cfg.overlap:
        # Sync EASGD1/2 baseline: force the exchange to wait for the
        # gradients (kills the paper's §6.1.3 overlap).
        state_params, grads = lax.optimization_barrier((state.params, grads))
        state = state._replace(params=state_params)

    def do_exchange(st, g):
        if cfg.packed and mesh is not None and param_specs is not None:
            return _exchange_packed(st, g, cfg, mesh, param_specs, pod_axis,
                                    plan=plan)
        return _exchange_unpacked(st, g, cfg)

    tau = cfg.easgd.tau
    if tau <= 1:
        return do_exchange(state, grads)
    return lax.cond(
        state.step % tau == 0,
        lambda s, g: do_exchange(s, g),
        lambda s, g: _momentum_only(s, g, cfg),
        state, grads,
    )
