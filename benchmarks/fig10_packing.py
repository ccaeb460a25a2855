"""Paper Fig 10 / §5.2: packed single-buffer vs per-layer communication.

Two measurements:
 1. α–β model (paper's own argument): L small messages vs 1 packed message
    on the paper's interconnects (Table 2) and on TPU ICI.
 2. REAL wall-clock microbenchmark on host devices: psum of L small arrays
    vs one packed flat buffer (8 host devices — the schedule effect is
    hardware-independent even if constants differ).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import csv_row
from repro.core import costmodel


# layer sizes of a LeNet-like net (paper's MNIST model): many small tensors
LENET_LAYER_BYTES = [600 * 4, 24 * 4, 2_400 * 4, 64 * 4, 150_000 * 4,
                     480 * 4, 40_000 * 4, 336 * 4, 3_360 * 4, 40 * 4]
# AlexNet-ish (paper Fig 10 uses AlexNet): 249 MB over ~16 tensors
ALEXNET_LAYER_BYTES = [
    35_000 * 4, 96 * 4, 614_000 * 4, 256 * 4, 885_000 * 4, 384 * 4,
    1_327_000 * 4, 384 * 4, 884_000 * 4, 256 * 4, 37_750_000 * 4,
    4_096 * 4, 16_777_000 * 4, 4_096 * 4, 4_096_000 * 4, 1_000 * 4,
]


def run_model(quick: bool = False):
    for net in (costmodel.MELLANOX_FDR, costmodel.INTEL_QDR,
                costmodel.INTEL_10GBE, costmodel.TPU_ICI):
        for name, sizes in (("lenet", LENET_LAYER_BYTES),
                            ("alexnet", ALEXNET_LAYER_BYTES)):
            p = 16
            t_unpacked = costmodel.t_per_layer(sizes, p, net)
            t_packed = costmodel.t_packed(sizes, p, net)
            csv_row(
                f"fig10/model/{net.name.replace(' ', '_')}/{name}",
                t_packed * 1e6,
                f"unpacked={t_unpacked*1e6:.1f}us;"
                f"speedup={t_unpacked/t_packed:.2f}x")


def run_measured(quick: bool = False):
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from repro.utils.timing import time_fn

    n_dev = jax.device_count()
    if n_dev < 2:
        csv_row("fig10/measured/skipped", 0.0, f"only {n_dev} device")
        return
    mesh = make_host_mesh((n_dev,), ("x",))
    sizes = [s // 4 for s in LENET_LAYER_BYTES]
    arrs = [jnp.ones((n_dev, s), jnp.float32) for s in sizes]
    packed = jnp.ones((n_dev, sum(sizes)), jnp.float32)
    from jax.sharding import PartitionSpec as P
    from functools import partial

    @partial(jax.shard_map, mesh=mesh, in_specs=(P("x"),) * len(arrs),
             out_specs=(P("x"),) * len(arrs), check_vma=False)
    def per_layer(*xs):
        return tuple(jax.lax.psum(x, "x") for x in xs)

    @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
             check_vma=False)
    def one_packed(x):
        return jax.lax.psum(x, "x")

    t_u = time_fn(jax.jit(per_layer), *arrs, iters=5)
    t_p = time_fn(jax.jit(one_packed), packed, iters=5)
    csv_row("fig10/measured/per_layer", t_u * 1e6, f"{len(arrs)}_psums")
    csv_row("fig10/measured/packed", t_p * 1e6,
            f"speedup={t_u/max(t_p,1e-12):.2f}x")


def main(quick: bool = False):
    run_model(quick)
    run_measured(quick)


if __name__ == "__main__":
    main()
