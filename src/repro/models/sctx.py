"""Activation-sharding context.

Model code is mesh-agnostic; the runtime installs the mesh and a spec
function here (active during tracing) and blocks call
``shard(x, *logical_axes)`` at layout-critical points (projection outputs,
block boundaries, FFN hidden, logits chunks). Without these constraints the
SPMD partitioner may choose replicated activations (measured: one
unconstrained QKV projection cost 18.5 GiB/device on the gemma3-4b probe).
Code that must place work per device itself (a kernel the partitioner
cannot split, under ``jax.shard_map``) reads the same mesh and specs with
``layout()``.

Logical activation axes: "batch", "seq", "embed", "heads", "kv_heads",
"head_dim", "ff", "vocab", "experts", "groups", "inner".
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Callable, NamedTuple

import jax
from jax.sharding import NamedSharding


class Layout(NamedTuple):
    mesh: Any
    spec: Callable    # (shape, logical axes) -> PartitionSpec on ``mesh``


_ctx = contextvars.ContextVar("activation_sharding", default=None)


def layout() -> Layout | None:
    """The installed mesh and spec function, or None."""
    return _ctx.get()


def shard(x, *logical):
    """Apply the installed constraint (no-op when none installed)."""
    lay = _ctx.get()
    if lay is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(lay.mesh, lay.spec(x.shape, logical)))


@contextlib.contextmanager
def use(mesh, spec):
    token = _ctx.set(Layout(mesh, spec))
    try:
        yield
    finally:
        _ctx.reset(token)
