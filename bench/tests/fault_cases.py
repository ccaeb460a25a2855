"""Drives whole runs of the harness on the CPU at a tiny size, without its
look for a chip, with the timed path intact or broken underneath, and
prints each run's ``correct`` and checks as one JSON line per case.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/fault_cases.py 4chip noexchange

Faults: "unchanged" (the step returns its state unchanged), "half" (each
worker's loss is the mean over the first half of its rows), "noexchange"
(the cross-worker sum left out), "answer" (the step's new weights altered
where the update produces them: one leaf 1% larger). "none" is the intact
program.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CELLS = {"1chip": ("phi3_b8s4096_1chip", "b8_s4096_1w", 1),
         "4chip": ("phi3_b8s4096_4chip_psum", "b8_s4096_4w_psum", 4)}
TINY = {"seq": 64, "batch_per_worker": 2}
ANSWER_SCALE = 1.01


def plant(fault: str) -> None:
    import jax

    from repro.comm import plan
    from repro.core import elastic
    from repro.models import transformer

    if fault == "unchanged":
        elastic.apply_gradients = \
            lambda state, grads, cfg, **kw: state._replace(step=state.step + 1)
    elif fault == "answer":
        apply = elastic.apply_gradients

        def altered(*args, **kw):
            new = apply(*args, **kw)
            leaves, tree = jax.tree_util.tree_flatten(new.params)
            leaves[0] = leaves[0] * ANSWER_SCALE
            return new._replace(
                params=jax.tree_util.tree_unflatten(tree, leaves))
        elastic.apply_gradients = altered
    elif fault == "half":
        lm_loss = transformer.lm_loss

        def broken(cfg, params, batch, extra_fwd_kwargs=None):
            n = batch["tokens"].shape[0] // 2
            return lm_loss(cfg, params, {k: v[:n] for k, v in batch.items()},
                           extra_fwd_kwargs)
        transformer.lm_loss = broken
    elif fault == "noexchange":
        plan.ExchangePlan.allreduce_sum = lambda self, x: x
    elif fault != "none":
        raise ValueError(fault)


def run_case(cell: str, fault: str, seed: int = 2**31 + 7) -> dict:
    import jax

    from bench import run, spec
    name, traffic_name, chips = CELLS[cell]
    plant(fault)
    bench = spec.benchmark()
    conf = spec.load_json(os.path.join(spec.HERE, "tests", "data",
                                       "phi3_tiny.json"))
    mod = spec.config_module(bench, "phi3_mini")
    traffic = dict(spec.traffic(traffic_name), **TINY)
    res = run.run_cell(conf, mod, traffic, spec.limits(name),
                       jax.devices()[:chips], seed, 0.2, False,
                       peaks={"bf16_flops_per_s": 1e12,
                              "hbm_bytes_per_s": 1e11})
    return {"cell": cell, "fault": fault, "correct": res["correct"],
            "checks": res["checks"]}


if __name__ == "__main__":
    print(json.dumps(run_case(sys.argv[1], sys.argv[2])), flush=True)
