"""The repro.comm contract: every registered schedule is (a) allreduce-
equivalent to lax.psum on a host device mesh, (b) priced by a cost function
that is monotone in message size, and (c) priced IDENTICALLY by the DES
engine for the same (P, bytes) — one registry, three consumers.
"""
import numpy as np
import pytest

from repro import comm
from repro.core import costmodel
from repro.core.async_engine import PSEngine, SimConfig
from repro.core.easgd import EASGDConfig

NET = costmodel.Network("test-net", 2e-6, 1 / 10e9)


# ---------------------------------------------------------------------------
# (a) runnable: schedule == psum on a real (host) mesh
# ---------------------------------------------------------------------------

def test_every_schedule_equals_psum(subproc):
    subproc("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro import comm
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((8,), ('x',))
        x = jnp.arange(96, dtype=jnp.float32) * 0.125 - 3.0
        want = np.asarray(x) * 8
        for algo in comm.names():
            out = comm.shard_map_allreduce(mesh, x, 'x', algo)
            np.testing.assert_allclose(np.asarray(out)[0], want, rtol=1e-6,
                                       err_msg=algo)
        # 'auto' resolves through comm.choose and must also be correct
        out = comm.shard_map_allreduce(mesh, x, 'x', 'auto')
        np.testing.assert_allclose(np.asarray(out)[0], want, rtol=1e-6)
        print('schedules OK')
    """)


def test_exchange_plan_runs_every_schedule(subproc):
    """ExchangePlan.exchange == cross-pod mean for every schedule, on a
    4-pod mesh, called inside shard_map (the runtime's usage pattern)."""
    subproc("""
        import warnings; warnings.filterwarnings('ignore')
        from functools import partial
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import comm
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((4,), ('pod',))
        vals = jnp.stack([jnp.full((6,), float(i)) for i in range(4)])
        for name in comm.names():
            plan = comm.make_plan(name, axis_name='pod', n_total=4)
            @partial(jax.shard_map, mesh=mesh, in_specs=P('pod'),
                     out_specs=P('pod'), check_vma=False)
            def f(x):
                tree = {'w': x[0]}
                return plan.exchange(tree)['w'][None]
            out = f(vals)
            want = np.full((6,), 1.5)  # mean of 0,1,2,3
            for row in np.asarray(out):
                np.testing.assert_allclose(row, want, rtol=1e-6,
                                           err_msg=name)
        print('exchange plans OK')
    """)


# ---------------------------------------------------------------------------
# (b) cost functions: monotone in bytes, sane in P
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(comm.names()))
def test_cost_monotone_in_bytes(name):
    sched = comm.get(name)
    for p in (2, 4, 8, 16):
        costs = [sched.cost(n, p, NET)
                 for n in (1e2, 1e4, 1e6, 1e8)]
        assert all(c > 0 for c in costs), (name, p, costs)
        assert costs == sorted(costs), (name, p, costs)
    assert sched.cost(1e6, 1, NET) == 0.0  # single participant: free


def test_cost_orderings_match_paper():
    """Θ(P) round-robin must dominate the log/ring schedules at scale, and
    psum (tuned-library best) must be the min of butterfly/ring."""
    n = 4e6
    for p in (4, 16, 64):
        rr = comm.get("round_robin").cost(n, p, NET)
        tree = comm.get("tree").cost(n, p, NET)
        ring = comm.get("ring").cost(n, p, NET)
        bfly = comm.get("butterfly").cost(n, p, NET)
        psum = comm.get("psum").cost(n, p, NET)
        assert rr > tree > bfly, (p, rr, tree, bfly)
        assert psum == min(bfly, ring)


# ---------------------------------------------------------------------------
# (c) the DES engine prices through the SAME registry
# ---------------------------------------------------------------------------

def _engine(n=1000, p=4, schedule="tree"):
    w0 = np.zeros(n)
    sim = SimConfig(n_workers=p, net=NET, compute_jitter=0.0,
                    schedule=schedule, t_compute=1e-6,
                    t_update_per_byte=0.0, eval_every_iters=10**9)
    return PSEngine(lambda w, s, i: np.zeros_like(w), lambda w: 0.0,
                    w0, EASGDConfig(), sim)


@pytest.mark.parametrize("name", list(comm.names()))
def test_engine_exchange_price_is_registry_price(name):
    eng = _engine(schedule=name)
    assert eng.t_exchange() == comm.get(name).cost(eng.nbytes, 4, NET)


@pytest.mark.parametrize("name", list(comm.names()))
def test_sync_sgd_charges_registry_cost_per_step(name):
    """Non-tautological: run the sync loop and check the clock was charged
    exactly steps × registry-cost (sync SGD cannot overlap its all-reduce)."""
    p, steps = 4, 5
    eng = _engine(p=p, schedule=name)
    r = eng.run("sync_sgd", total_iters=p * steps)
    want = steps * comm.get(name).cost(eng.nbytes, p, NET)
    np.testing.assert_allclose(r.breakdown["param_comm"], want, rtol=1e-12)


def test_original_easgd_full_cycle_is_round_robin_cost():
    """P iterations of Original EASGD = one full round-robin cycle under
    the registry's pricing."""
    p = 4
    eng = _engine(p=p, schedule="tree")
    r = eng.run("original_easgd", total_iters=p)
    want = comm.get("round_robin").cost(eng.nbytes, p, NET)
    np.testing.assert_allclose(r.breakdown["param_comm"], want, rtol=1e-12)


# ---------------------------------------------------------------------------
# plan-level wire accounting
# ---------------------------------------------------------------------------

def test_plan_compression_shrinks_wire_and_cost():
    none = comm.make_plan("ring", "none", n_total=8)
    sign = comm.make_plan("ring", "sign_ef", n_total=8)
    n_elems = 1_000_000
    # jit accounting: signs cross the mesh as int8 (the in-flight sum must
    # address them) — 4x fewer bytes than f32, matching the compiled HLO
    assert sign.wire_bytes(n_elems) == pytest.approx(
        none.wire_bytes(n_elems) / 4, rel=1e-6)
    # framed accounting: the repro.net byte-stream wire bit-packs for real
    assert sign.framed_wire_bytes(n_elems) < \
        none.framed_wire_bytes(n_elems) / 8
    assert sign.cost_s(n_elems, NET) < none.cost_s(n_elems, NET)


def test_plan_overlap_hides_comm():
    plan = comm.make_plan("tree", overlap=True, n_total=8)
    blocking = comm.make_plan("tree", overlap=False, n_total=8)
    n_elems = 1_000_000
    t = plan.cost_s(n_elems, NET)
    assert plan.visible_cost_s(n_elems, NET, t_compute=2 * t) == 0.0
    assert blocking.visible_cost_s(n_elems, NET, t_compute=2 * t) == t


# ---------------------------------------------------------------------------
# first-class hierarchical schedule + ElasticConfig schedule="auto"
# ---------------------------------------------------------------------------

def test_hierarchical_is_registered_and_selectable():
    assert "hierarchical" in comm.names()
    from repro.core.elastic import ElasticConfig
    cfg = ElasticConfig(schedule="hierarchical")
    plan = cfg.exchange_plan("pod", 8)
    assert plan.schedule.name == "hierarchical"
    # pow2-only constraint surfaces at plan build, not deep in tracing
    with pytest.raises(ValueError, match="power-of-two"):
        comm.make_plan("hierarchical", axis_name="pod", n_total=6)


def test_elastic_auto_schedule_resolution():
    """schedule='auto' resolves through comm.choose from the packed wire
    bytes and pod count at build time (latency-bound → butterfly,
    bandwidth-bound → ring), and stays lazy without a buffer size."""
    from repro.core import costmodel
    from repro.core.elastic import ElasticConfig
    cfg = ElasticConfig(schedule="auto")
    assert cfg.resolve_schedule(8, 100) == "butterfly"
    assert cfg.resolve_schedule(8, 50_000_000) == "ring"
    assert cfg.resolve_schedule(8, 100) == comm.choose(
        400, 8, costmodel.TPU_DCI)
    assert cfg.resolve_schedule(1, 100) == "psum"       # single pod
    assert cfg.resolve_schedule(8, None) == "psum"      # size unknown
    plan = cfg.exchange_plan(None, 8, n_elements=50_000_000)
    assert plan.schedule.name == "ring"
    # compression shrinks the wire bytes the chooser sees
    sign = ElasticConfig(schedule="auto", compression="sign_ef")
    assert sign.resolve_schedule(8, 3000) == "butterfly"


def test_auto_schedule_builds_train_step(subproc):
    subproc("""
        import warnings; warnings.filterwarnings('ignore')
        import jax
        from repro import configs
        from repro.core.easgd import EASGDConfig
        from repro.core.elastic import ElasticConfig
        from repro.runtime.train import build_train_step
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((2, 2, 2), ('pod', 'data', 'model'))
        cfg = configs.get('gemma3-4b').reduced
        build = build_train_step(
            cfg, ElasticConfig(easgd=EASGDConfig(), schedule='auto'), mesh,
            n_pods=2, per_pod_batch=4, seq=16)
        name = build.exchange_plan.schedule.name
        assert name in ('butterfly', 'ring'), name
        print('auto resolved to', name)
    """)
