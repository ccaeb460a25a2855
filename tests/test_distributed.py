"""Multi-device integration tests (subprocess with 8 host devices):
collective schedule equivalence, sharded train-step vs reference, sharded
serve vs reference, multi-pod EASGD semantics, reduced-mesh dry-run smoke."""
import pytest


def test_collective_schedules_equal_psum(subproc):
    subproc("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import collectives
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((8,), ('x',))
        x = jnp.arange(64, dtype=jnp.float32) * 0.25 - 3.0
        for algo in ['psum', 'butterfly', 'ring', 'round_robin']:
            out = collectives.shard_map_allreduce(mesh, x, 'x', algo)
            np.testing.assert_allclose(np.asarray(out)[0],
                                       np.asarray(x) * 8, rtol=1e-6)
        print('collectives OK')
    """)


def test_hierarchical_allreduce(subproc):
    subproc("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.core import collectives
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((2, 4), ('pod', 'data'))
        @partial(jax.shard_map, mesh=mesh, in_specs=P(('pod', 'data')),
                 out_specs=P(('pod', 'data')), check_vma=False)
        def f(x):
            # local shard is this device's 16-element row
            return collectives.hierarchical_allreduce(
                x, 'data', 'pod', inner='ring', outer='butterfly')
        x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
        out = f(x.reshape(-1))
        want = x.sum(0)
        np.testing.assert_allclose(np.asarray(out).reshape(8, 16)[0], want,
                                   rtol=1e-6)
        print('hierarchical OK')
    """)


def test_multipod_train_step_matches_reference(subproc):
    subproc("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.core.easgd import EASGDConfig
        from repro.core.elastic import ElasticConfig
        from repro.core import elastic
        from repro.runtime.train import build_train_step
        from repro.models import transformer as tfm
        from repro.models.common import init_params

        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((2, 2, 2), ('pod', 'data', 'model'))
        cfg = configs.get('gemma3-4b').reduced
        ecfg = ElasticConfig(easgd=EASGDConfig(eta=0.05, rho=0.02, mu=0.9),
                             packed=True)
        build = build_train_step(cfg, ecfg, mesh, n_pods=2, per_pod_batch=4,
                                 seq=16, microbatches=2)
        state = build.init_state()
        key = jax.random.PRNGKey(7)
        tokens = jax.random.randint(key, (2, 4, 16), 0, cfg.vocab_size)
        batch = {'tokens': tokens, 'targets': jnp.roll(tokens, -1, -1),
                 'mask': jnp.ones((2, 4, 16), jnp.float32)}
        state1, metrics = build.step(state, batch)
        assert np.isfinite(metrics['loss'])

        # reference: unsharded, no microbatching, unpacked exchange
        params = init_params(tfm.model_defs(cfg), jax.random.PRNGKey(0),
                             cfg.param_dtype)
        st_ref = elastic.init(params, ecfg, 2)
        gfn = jax.vmap(jax.value_and_grad(
            lambda p, b: tfm.lm_loss(cfg, p, b), has_aux=True))
        (_, _), grads = gfn(st_ref.params, batch)
        st_ref1 = elastic.apply_gradients(
            st_ref, grads, ElasticConfig(easgd=ecfg.easgd, packed=False))
        err = max(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree_util.tree_leaves(state1.params),
                            jax.tree_util.tree_leaves(st_ref1.params)))
        assert err < 5e-3, err   # bf16 reduction-order noise only
        print('multipod train OK, err', err)
    """, timeout=1200)


def test_sharded_serve_matches_reference(subproc):
    subproc("""
        import warnings; warnings.filterwarnings('ignore')
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.runtime.serve import build_serve_steps
        from repro.models import transformer as tfm
        from repro.models.common import init_params

        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((4, 2), ('data', 'model'))
        cfg = dataclasses.replace(configs.get('deepseek-v2-236b').reduced,
                                  compute_dtype=jnp.float32)
        B, L = 8, 32
        build = build_serve_steps(cfg, mesh, batch=B, max_len=L)
        params = init_params(tfm.model_defs(cfg), jax.random.PRNGKey(0),
                             cfg.param_dtype)
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, L - 4), 0,
                                  cfg.vocab_size)
        logits, caches = build.prefill(params, toks, {})
        pos = jnp.full((B,), L - 4, jnp.int32)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        logits2, caches = build.decode(params, caches, tok, pos, {})
        caches_ref = tfm.init_caches(cfg, B, L)
        lg_ref, caches_ref = tfm.prefill(cfg, params, toks, caches_ref)
        lg2_ref, _ = tfm.decode_step(cfg, params, tok, caches_ref, pos)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(lg_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(logits2), np.asarray(lg2_ref),
                                   rtol=1e-4, atol=1e-4)
        print('sharded serve OK')
    """, timeout=1200)


def test_dryrun_smoke_reduced_mesh(subproc):
    """lower+compile reduced configs for train & decode on an 8-dev
    multi-pod mesh, with memory/cost/collective extraction — the dry-run
    machinery end-to-end."""
    subproc("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.core.easgd import EASGDConfig
        from repro.core.elastic import ElasticConfig
        from repro.runtime.train import build_train_step, make_batch_defs
        from repro.runtime.serve import build_serve_steps
        from repro.launch import hloparse

        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((2, 2, 2), ('pod', 'data', 'model'))
        for aid in ['recurrentgemma-2b', 'grok-1-314b']:
            cfg = configs.get(aid).reduced
            build = build_train_step(
                cfg, ElasticConfig(easgd=EASGDConfig()), mesh, n_pods=2,
                per_pod_batch=4, seq=16, microbatches=2)
            lowered = build.step.lower(build.abstract_state,
                                       make_batch_defs(cfg, 2, 4, 16))
            compiled = lowered.compile()
            ma = compiled.memory_analysis()
            assert ma.temp_size_in_bytes >= 0
            pc = hloparse.parse_costs(compiled.as_text())
            assert pc.flops > 0
            print(aid, 'train lower+compile OK, collective bytes',
                  pc.collective_bytes)

        cfg = configs.get('mamba2-780m').reduced
        from repro.launch.mesh import make_host_mesh
        mesh2 = make_host_mesh((4, 2), ('data', 'model'))
        sb = build_serve_steps(cfg, mesh2, batch=8, max_len=64)
        tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((8,), jnp.int32)
        compiled = sb.decode.lower(sb.abstract_params, sb.abstract_caches,
                                   tok, pos, {}).compile()
        print('decode lower+compile OK')
    """, timeout=1800)
