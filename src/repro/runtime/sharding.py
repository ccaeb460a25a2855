"""Sharding rules: logical axes → mesh PartitionSpecs for params, batches,
and serving caches (DESIGN.md §5).

Mesh axes: optional ``pod`` (EASGD workers), ``data`` (intra-pod DP/FSDP),
``model`` (TP/EP). All divisibility checks happen here so every arch maps
onto the fixed production mesh without invalid shardings (e.g. 20 heads on a
16-way model axis → attention replicates, FFN/vocab still shard).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer as tfm
from repro.models.common import ModelConfig, make_rules, partition_specs


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def param_specs(cfg: ModelConfig, mesh):
    """PartitionSpecs for the model parameter pytree (no pod dim)."""
    sizes = mesh_axis_sizes(mesh)
    rules = make_rules(cfg, sizes)
    return partition_specs(tfm.model_defs(cfg), rules)


def _div(n: int, size: int) -> bool:
    return size > 1 and n % size == 0


def batch_specs(cfg: ModelConfig, mesh, *, pod_dim: bool):
    """Specs for a training batch with leading (n_pods, B_local, S) dims."""
    pod = "pod" if (pod_dim and "pod" in mesh.axis_names) else None
    tok = P(pod, "data", None)
    specs = {"tokens": tok, "targets": tok, "mask": tok}
    if cfg.mrope_sections is not None:
        specs["mrope_positions"] = P(pod, None, "data", None)
    if cfg.patch_embed_tokens:
        specs["patch_embeds"] = P(pod, "data", None, None)
    return specs


def serve_token_specs(cfg: ModelConfig, mesh, B: int):
    sizes = mesh_axis_sizes(mesh)
    b_ax = "data" if _div(B, sizes.get("data", 1)) else None
    return P(b_ax, None)


def cache_specs(cfg: ModelConfig, mesh, B: int, max_len: int):
    """PartitionSpecs mirroring transformer.init_cache_defs.

    Batch shards over `data` when divisible; otherwise (long-context decode
    with B=1) the SEQUENCE dim of attention/MLA caches shards over `data`
    — flash-decoding style: GSPMD reduces the partial softmax terms.
    Head/feature dims shard over `model` when divisible.
    """
    sizes = mesh_axis_sizes(mesh)
    dsz, msz = sizes.get("data", 1), sizes.get("model", 1)
    D = cfg.resolved_head_dim
    b_ax = "data" if _div(B, dsz) else None

    def seq_ax(S, *, model_free: bool):
        """Shard the cache's TIME dim over every axis not already used:
        `data` when the batch can't take it (long-context B=1), `model`
        when the kv-head/feature dim can't (GQA kv < model size). Partial
        softmax over the sharded seq dim is a GSPMD reduction
        (flash-decoding)."""
        axes = []
        if b_ax is None and _div(S, dsz):
            axes.append("data")
        if model_free and _div(S, msz * (dsz if axes else 1)):
            axes.append("model")
        if not axes:
            return None
        return tuple(axes) if len(axes) > 1 else axes[0]

    def kind_spec(kind: str):
        if kind in ("attn", "local"):
            S = max_len if kind == "attn" else min(cfg.window, max_len)
            kv_ax = "model" if _div(cfg.n_kv_heads, msz) else None
            s = P(b_ax, seq_ax(S, model_free=kv_ax is None), kv_ax, None)
            return {"k": s, "v": s}
        if kind == "mla":
            a = cfg.mla
            rank_ax = "model" if _div(a.kv_lora_rank, msz) else None
            return {
                "ckv": P(b_ax, seq_ax(max_len, model_free=rank_ax is None),
                         rank_ax),
                "kpe": P(b_ax, seq_ax(max_len, model_free=False), None),
            }
        if kind == "ssm":
            s = cfg.ssm
            d_inner = s.expand * cfg.d_model
            H = d_inner // s.head_dim
            conv_dim = d_inner + 2 * s.d_state
            return {
                "conv": P(b_ax, None,
                          "model" if _div(conv_dim, msz) else None),
                "state": P(b_ax, "model" if _div(H, msz) else None, None,
                           None),
            }
        if kind == "rglru":
            g = cfg.rglru
            w_ax = "model" if _div(g.width, msz) else None
            return {"conv": P(b_ax, None, w_ax), "state": P(b_ax, w_ax)}
        raise ValueError(kind)

    def stack(spec_tree):
        return jax.tree_util.tree_map(lambda s: P(None, *s), spec_tree,
                                      is_leaf=lambda x: isinstance(x, P))

    return {
        "stacked": tuple(stack(kind_spec(k)) for k in cfg.pattern),
        "rem": tuple(kind_spec(k) for k in cfg.remainder_kinds),
    }


def named(mesh, spec_tree):
    """Wrap a PartitionSpec pytree into NamedShardings for jit."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def activation_spec(cfg: ModelConfig, mesh):
    """Build the models.sctx spec fn: logical activation axes →
    PartitionSpec on this mesh. batch/groups→data, heads/ff/vocab/inner→
    model, experts_dp→data (EP buffers; takes priority over groups so the
    dispatch buffer resharding is the token all-to-all). Dims that don't
    divide their axis stay replicated."""
    sizes = mesh_axis_sizes(mesh)
    dsz, msz = sizes.get("data", 1), sizes.get("model", 1)

    data_axes = {"experts_dp": 0, "batch": 2, "groups": 2}
    model_axes = {"heads": 1, "kv_heads": 1, "ff": 1, "vocab": 1,
                  "experts": 1, "inner": 1}

    def spec(shape, logical):
        axes = [None] * len(logical)
        used = set()
        order = sorted(
            range(len(logical)),
            key=lambda i: data_axes.get(logical[i],
                                        model_axes.get(logical[i], 9)))
        for i in order:
            dim, name = shape[i], logical[i]
            if name in data_axes and "data" not in used and dim % dsz == 0:
                axes[i] = "data"
                used.add("data")
            elif name in model_axes and "model" not in used \
                    and dim % msz == 0:
                axes[i] = "model"
                used.add("model")
        return P(*axes)

    return spec


def block_constrainer(cfg: ModelConfig, mesh):
    """Streaming-FSDP gather: returns ``constrain(kind, params_subtree)``
    that re-shards one layer's params to their COMPUTE layout (TP only, no
    `data` factor). Inside the layer scan this forces exactly one weight
    all-gather per layer per pass — and its transpose in backward is the
    reduce-scatter of the weight grads (ZeRO semantics). Without it, the
    SPMD partitioner may all-reduce activations instead (measured 20×
    worse on the gemma3-4b probe). Returns None when cfg.fsdp is off.
    """
    if not cfg.fsdp:
        return None
    from repro.models import transformer as tfm
    from repro.models.common import make_rules, partition_specs

    sizes = mesh_axis_sizes(mesh)
    rules = make_rules(cfg, sizes)
    rules.pop("_fsdp_axis", None)
    # flatten specs once (P is tuple-like, so flatten with an explicit leaf
    # predicate and zip against the array leaves — structures mirror)
    spec_cache = {}
    for kind in set(cfg.pattern) | set(cfg.remainder_kinds):
        tree = partition_specs(tfm._block_defs(cfg, kind), rules)
        spec_cache[kind] = jax.tree_util.tree_flatten(
            tree, is_leaf=lambda x: isinstance(x, P))[0]

    def constrain(kind, subtree):
        leaves, treedef = jax.tree_util.tree_flatten(subtree)
        specs = spec_cache[kind]
        assert len(leaves) == len(specs), (kind, len(leaves), len(specs))
        out = [
            jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))
            for x, s in zip(leaves, specs)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    return constrain
