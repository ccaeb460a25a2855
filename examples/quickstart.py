"""Quickstart: train a small gemma3-family LM with multi-pod Sync EASGD on
CPU host devices, then decode from it.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py
"""
import warnings

warnings.filterwarnings("ignore")

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.easgd import EASGDConfig
from repro.core.elastic import ElasticConfig
from repro.data import ShardedPipeline, SyntheticLMStream
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tfm
from repro.runtime.train import build_train_step


def main():
    n_dev = jax.device_count()
    print(f"devices: {n_dev}")
    if n_dev >= 8:
        mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
        n_pods = 2
    else:
        mesh = make_host_mesh((1, 1), ("data", "model"))
        n_pods = 1

    cfg = configs.get("gemma3-4b").reduced
    ecfg = ElasticConfig(easgd=EASGDConfig(eta=0.15, rho=0.02, mu=0.9))
    B, S = 16, 32
    build = build_train_step(cfg, ecfg, mesh, n_pods=n_pods,
                             per_pod_batch=B // n_pods, seq=S)
    state = build.init_state()

    pipe = ShardedPipeline(
        lambda shard, n: SyntheticLMStream(cfg.vocab_size, S, B // n_pods,
                                           seed=3, shard=shard, n_shards=n),
        n_pods=n_pods).start()
    print("training 40 steps of Sync EASGD "
          f"({n_pods} pods × {B // n_pods} seqs × {S} tokens)…")
    try:
        for step in range(40):
            batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
            state, metrics = build.step(state, batch)
            if step % 8 == 0:
                print(f"  step {step:3d}  loss {float(metrics['loss']):.4f} "
                      f"acc {float(metrics['accuracy']):.3f}")
    finally:
        pipe.stop()
    print(f"final loss {float(metrics['loss']):.4f}")

    # decode a few tokens from the CENTER weights (the durable consensus)
    params = jax.tree_util.tree_map(lambda c: c, state.center)
    caches = tfm.init_caches(cfg, 1, max_len=16)
    tok = jnp.zeros((1, 1), jnp.int32)
    out = []
    for t in range(8):
        logits, caches = tfm.decode_step(
            cfg, params, tok, caches, jnp.asarray([t], jnp.int32))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(int(tok[0, 0]))
    print("greedy decode from center weights:", out)


if __name__ == "__main__":
    main()
