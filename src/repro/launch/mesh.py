"""Mesh definitions. Every axis is ``AxisType.Auto``: the model code places
activations with ``with_sharding_constraint`` and lets GSPMD propagate the
rest, which jax refuses on ``Explicit`` axes (the ``jax.make_mesh`` default).

Single pod: (data=16, model=16) = 256 chips (one TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips — the `pod` axis carries
the EASGD elastic exchange (slow cross-pod links), `data`/`model` stay
inside a pod (fast ICI).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_host_mesh(axis_shapes, axis_names, *, devices=None):
    """A mesh over ``devices`` (default: all of them) with Auto axes."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_host_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_host_mesh((16, 16), ("data", "model"))


def n_pods_of(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1)
