"""The harness's initialisation is the program's ``init_state``, with the
key as an argument, so that a new seed compiles nothing in set-up."""
import os

import jax
import numpy as np

from bench import run, spec

HERE = os.path.dirname(os.path.abspath(__file__))


def _build(seed):
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.train import build_train_step
    conf = spec.load_json(os.path.join(HERE, "data", "phi3_tiny.json"))
    traffic = dict(spec.traffic("b8_s4096_1w"), seq=32, batch_per_worker=2)
    cfg = run.program_config(conf)
    ecfg = run.elastic_config(traffic)
    mesh = make_host_mesh((1, 1), ("data", "model"),
                          devices=jax.devices()[:1])
    build = build_train_step(cfg, ecfg, mesh, n_pods=1, per_pod_batch=2,
                             seq=32, seed=seed)
    return build, run.keyed_init(cfg, ecfg, mesh, build)


def test_keyed_init_is_the_programs_init():
    build, init = _build(2**31 + 7)
    want = jax.tree_util.tree_leaves(build.init_state())
    have = jax.tree_util.tree_leaves(init(jax.random.PRNGKey(2**31 + 7)))
    assert len(want) == len(have)
    for a, b in zip(want, have):
        assert a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_new_seed_compiles_nothing():
    _, init = _build(0)
    jax.block_until_ready(init(jax.random.PRNGKey(1)))
    with run.count_compiles() as n:
        jax.block_until_ready(init(jax.random.PRNGKey(123456789)))
    assert n[0] == 0
