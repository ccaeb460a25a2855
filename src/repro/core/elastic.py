"""Multi-pod Sync EASGD — the paper's technique as a first-class JAX module.

Mapping (DESIGN.md §2): each **pod** is one EASGD worker. Inside a pod the
gradient is reduced synchronously over the `data` axis (fast ICI — GSPMD does
this automatically from the batch sharding). Across pods, workers exchange
*weights, not gradients*, every ``tau`` steps through the elastic-averaging
rules (paper eqs. 1–2 / 5–6), using the paper's three co-design techniques:

 1. **Packed single-buffer exchange** (paper §5.2): inside ``shard_map`` each
    device flattens its *local shards* of the one message, δ = W − W̄, into
    one contiguous buffer and issues a SINGLE cross-pod all-reduce. Packing
    in shard-space is a pure local reshape — no resharding traffic — while
    guaranteeing one collective (one α) instead of one per tensor. W, V and
    W̄ never cross pods and are updated leaf by leaf; with no collective and
    no codec nothing is packed.
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
# flat (packed) buffers — only for what crosses the pod axis
# ---------------------------------------------------------------------------

def _pack_local(tree, pods: int):
    """Flatten a pytree of local shards, each with a leading pod dim of size
    ``pods``, into one contiguous fp32 buffer (pods, n); the pod dim stays
    outer.

    Inside shard_map this is a per-device reshape+concat: zero communication.
    This IS the paper's 'single-layer layout' (§5.2) adapted to shard-space.
    """
    return jnp.concatenate(
        [l.reshape(pods, -1).astype(jnp.float32)
         for l in jax.tree_util.tree_leaves(tree)], axis=1
    )


def _unpack_local(buf, template, pods: int | None = None):
    """Slice ``buf`` back into ``template``'s tree and shapes, keeping the
    buffer's dtype; with ``pods`` the buffer is (pods, n), else (n,)."""
    leaves, treedef = jax.tree_util.tree_flatten(template)
    out, off = [], 0
    for l in leaves:
        if pods is None:
            size = l.size
            chunk = lax.slice_in_dim(buf, off, off + size)
        else:
            size = l.size // pods
            chunk = lax.slice_in_dim(buf, off, off + size, axis=1)
        out.append(chunk.reshape(l.shape))
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


def _elastic_tensors(params, momentum, center, grads, mean_w, e, n_pods):
    """Eqs 5–6 and 2 leaf by leaf, given the cross-pod mean of W_t:
    v′ = μv − ηg, w′ = w + v′ − ηρ(w − w̄), w̄′ = w̄ + αP(m − w̄), all in
    float32 (w′ takes v′ before its rounding to V's dtype), each result
    stored at its state's dtype. Returns (W′, V′, W̄′)."""
    f32 = lambda x: x.astype(jnp.float32)
    v32 = tree_map(lambda v, g: e.mu * f32(v) - e.eta * f32(g),
                   momentum, grads)
    p_new = tree_map(
        lambda w, v, c: (f32(w) + v - e.eta * e.rho * (f32(w) - f32(c)[None])
                         ).astype(w.dtype),
        params, v32, center,
    )
    a = e.alpha * n_pods
    c_new = tree_map(
        lambda c, m: (f32(c) + a * (f32(m) - f32(c))).astype(c.dtype),
        center, mean_w,
    )
    v_new = tree_map(lambda v, old: v.astype(old.dtype), v32, momentum)
    return p_new, v_new, c_new


def _exchange_unpacked(state, grads, cfg):
    """Per-tensor cross-pod mean: one collective per parameter (the paper's
    'multiple rounds of communication for different layers' baseline).
    GSPMD may still combine small all-reduces; the packed path below makes
    the single message structural."""
    mean_w = tree_map(lambda w: jnp.mean(w.astype(jnp.float32), axis=0),
                      state.params)
    p_new, v_new, c_new = _elastic_tensors(
        state.params, state.momentum, state.center, grads, mean_w,
        cfg.easgd, n_pods_of(state))
    return state._replace(step=state.step + 1, params=p_new, momentum=v_new,
                          center=c_new)


def _exchange_packed(state, grads, cfg, mesh, param_specs, pod_axis,
                     plan=None):
    """Single-message exchange inside shard_map (paper §5.2 + §6.1).

    Every device forms δ = W_t − W̄ per leaf. Where δ crosses the pod axis
    (the plan has an axis) or a codec encodes it, it (a) packs its shards of
    δ into one flat buffer, (b) runs ONE collective over the pod axis with
    the plan's registered schedule (repro.comm — tree, ring, …), compressed
    with error feedback if the codec says so, and (c) unpacks mean(δ) into
    W̄'s tree; otherwise mean(δ) is the local mean over the leaf's pod dim,
    and nothing is packed. Then m = W̄ + mean(δ), and eqs 5–6 and 2 update
    W, V and W̄ leaf by leaf, in place where the state is donated.
    """
    n_pods = n_pods_of(state)
    pod_in_mesh = pod_axis is not None and pod_axis in mesh.axis_names
    if plan is None:
        n_elems = sum(l.size for l in
                      jax.tree_util.tree_leaves(state.params)) // n_pods
        plan = cfg.exchange_plan(
            axis_name=pod_axis if (n_pods > 1 and pod_in_mesh) else None,
            n_total=n_pods, n_elements=n_elems)
    flat = plan.axis_name is not None or cfg.compression != "none"

    specs = state_specs(param_specs, cfg,
                        pod_axis if (n_pods > 1 and pod_in_mesh) else None)
    ef_spec = specs.ef_error if cfg.compression != "none" else P()

    def body(step, params, momentum, center, ef, g):
        # local shards; pod-dim is size n_pods/|pod axis| locally (=1 on the
        # production mesh). The phases carry named scopes (elastic.pack,
        # .exchange, .unpack, .update), so a profiler trace can split δ's
        # flat copies from the arithmetic.
        local_pods = jax.tree_util.tree_leaves(params)[0].shape[0]
        delta = lambda w_tree, c_tree: tree_map(
            lambda w, c: (w.astype(jnp.float32)
                          - c.astype(jnp.float32)[None]), w_tree, c_tree)
        ef_new = ef
        if flat:
            with jax.named_scope("elastic.pack"):
                # δ from barrier-renamed W and W̄: XLA then forms it inside
                # the pack's copy instead of sharing it with the update's
                # w − w̄ and holding δ's leaves live until the pack
                d2 = _pack_local(delta(*lax.optimization_barrier(
                    (params, center))), local_pods)     # (local_pods, n)
            # --- the ONE cross-pod collective (schedule × compression) ----
            with jax.named_scope("elastic.exchange"):
                if cfg.compression != "none":
                    mean_d, ef2 = plan.reduce_mean_flat(
                        d2, _pack_local(ef, local_pods))
                    ef_new = _unpack_local(ef2, ef, local_pods)
                else:
                    mean_d, _ = plan.reduce_mean_flat(d2)
            with jax.named_scope("elastic.unpack"):
                mean_d = _unpack_local(mean_d, center)
        else:
            with jax.named_scope("elastic.update"):
                mean_d = tree_map(lambda d: jnp.sum(d, axis=0) / float(n_pods),
                                  delta(params, center))

        # --- per-leaf elementwise update (eqs 5–6 + 2) ---------------------
        with jax.named_scope("elastic.update"):
            mean_w = tree_map(lambda c, d: c.astype(jnp.float32) + d,
                              center, mean_d)
            p_new, v_new, c_new = _elastic_tensors(
                params, momentum, center, g, mean_w, cfg.easgd, n_pods)
        return step + 1, p_new, v_new, c_new, ef_new

    in_specs = (P(), specs.params, specs.momentum, specs.center, ef_spec,
                specs.params)
    shmapped = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), specs.params, specs.momentum, specs.center, ef_spec),
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

    # each gradient reaches the update through HBM, so the update stays a
    # pass of its own: left free, XLA fuses a leaf's update into the matmul
    # that writes its gradient, and a trace times the update as fwd/bwd
    grads = tree_map(lax.optimization_barrier, grads)
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
