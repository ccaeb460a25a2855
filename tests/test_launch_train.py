"""The sync training entry point (``launch.train.run_sync``) end to end on
the CPU: Auto mesh axes, finite losses, and one compile of the step; and
where its persistent compile cache goes."""
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(subproc, tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and entries land there; without
    it the cache is ``<checkout>/.jax_cache`` (only the setting is checked,
    so the test writes nothing into the checkout)."""
    env_dir = str(tmp_path / "cache") if from_env else None
    expect = env_dir or os.path.join(REPO, ".jax_cache")
    out = subproc(f"""
        import os
        os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)
        if {env_dir!r}:
            os.environ['JAX_COMPILATION_CACHE_DIR'] = {env_dir!r}
        import jax
        import jax.numpy as jnp
        from repro.launch.train import use_compile_cache
        assert use_compile_cache() == {expect!r}
        assert jax.config.jax_compilation_cache_dir == {expect!r}
        if {env_dir!r}:
            jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
            jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
            assert os.listdir({env_dir!r}), 'nothing cached'
        print('CACHE_OK')
    """, n_devices=1, timeout=300)
    assert "CACHE_OK" in out


@pytest.mark.parametrize("n_pods,n_devices", [(1, 1), (2, 4)])
def test_run_sync_compiles_step_once(subproc, n_pods, n_devices):
    out = subproc(f"""
        import warnings; warnings.filterwarnings('ignore')
        import math
        from jax.sharding import AxisType
        from repro import configs
        from repro.core.easgd import EASGDConfig
        from repro.core.elastic import ElasticConfig
        from repro.launch.train import run_sync

        cfg = configs.get('phi3-mini-3.8b').reduced
        ecfg = ElasticConfig(easgd=EASGDConfig(eta=0.02, rho=0.01, mu=0.9))
        run = run_sync(cfg, ecfg, steps=3, batch=4, seq=32,
                       n_pods={n_pods}, log_every=1)
        assert all(t == AxisType.Auto for t in run.mesh.axis_types), \\
            run.mesh.axis_types
        assert dict(run.mesh.shape).get('pod', 1) == {n_pods}, run.mesh
        assert run.mesh.devices.size == {n_devices}, run.mesh
        assert len(run.losses) == 3, run.losses
        assert all(math.isfinite(x) for x in run.losses), run.losses
        # init_state is built with the step's shardings, so step 1 reuses
        # step 0's executable
        assert run.step_compiles == 1, run.step_compiles
        print('RUN_SYNC_OK', run.losses)
    """, n_devices=n_devices, timeout=600)
    assert "RUN_SYNC_OK" in out


def test_run_sync_steps_on_the_profiler_trace(subproc, tmp_path):
    """Under ``jax.profiler.trace``, each step of ``run_sync`` is a "train"
    step event with its number, and the pipeline's spans are there too."""
    out = subproc(f"""
        import glob
        import warnings; warnings.filterwarnings('ignore')
        import jax
        from jax.profiler import ProfileData
        from repro import configs
        from repro.core.easgd import EASGDConfig
        from repro.core.elastic import ElasticConfig
        from repro.launch.train import run_sync

        cfg = configs.get('phi3-mini-3.8b').reduced
        ecfg = ElasticConfig(easgd=EASGDConfig(eta=0.02, rho=0.01, mu=0.9))
        with jax.profiler.trace({str(tmp_path)!r}):
            run_sync(cfg, ecfg, steps=3, batch=2, seq=16, log_every=10)
        path = glob.glob({str(tmp_path)!r} + '/**/*.xplane.pb',
                         recursive=True)[0]
        events = [e for plane in ProfileData.from_file(path).planes
                  for line in plane.lines for e in line.events]
        steps = sorted(dict(e.stats)['step_num'] for e in events
                       if e.name == 'train')
        assert steps == [0, 1, 2], steps
        assert any(e.name == 'repro.input.produce' for e in events)
        print('STEP_EVENTS_OK')
    """, n_devices=1, timeout=600)
    assert "STEP_EVENTS_OK" in out
