"""The loop-aware HLO cost parser vs XLA cost_analysis (loop-free graphs)
and vs ground truth on scans."""
import warnings

warnings.filterwarnings("ignore")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import hloparse


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_matches_cost_analysis_loop_free():
    n = 256
    w1 = jnp.ones((n, n))
    w2 = jnp.ones((n, 2 * n))

    def f(x):
        return jax.nn.relu(x @ w1) @ w2

    c = _compile(f, jnp.ones((8, n)))
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):   # jax 0.4.x returns [dict]
        ca = ca[0]
    pc = hloparse.parse_costs(c.as_text())
    np.testing.assert_allclose(pc.flops, ca["flops"], rtol=0.05)


def test_scan_flops_multiplied():
    n, k = 128, 9
    w = jnp.ones((n, n))

    def f(x):
        out, _ = jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=k)
        return out

    c = _compile(f, jnp.ones((n, n)))
    pc = hloparse.parse_costs(c.as_text())
    np.testing.assert_allclose(pc.flops, 2 * n**3 * k, rtol=0.01)
    assert k in pc.while_trip_counts.values()


def test_nested_scan_flops():
    n, ko, ki = 128, 5, 3
    w = jnp.ones((n, n))

    def f(x):
        def outer(c, _):
            c2, _ = jax.lax.scan(lambda c3, _: (c3 @ w, None), c, None,
                                 length=ki)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=ko)
        return out

    c = _compile(f, jnp.ones((n, n)))
    pc = hloparse.parse_costs(c.as_text())
    np.testing.assert_allclose(pc.flops, 2 * n**3 * ko * ki, rtol=0.01)


def test_collectives_counted_with_trips(subproc):
    subproc("""
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch import hloparse
        import numpy as np
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((8,), ('x',))
        @partial(jax.shard_map, mesh=mesh, in_specs=P('x'), out_specs=P('x'),
                 check_vma=False)
        def body(x):
            def step(c, _):
                return jax.lax.psum(c, 'x') * 0.1, None
            out, _ = jax.lax.scan(step, x, None, length=5)
            return out
        c = jax.jit(body).lower(jnp.ones((8, 1024))).compile()
        pc = hloparse.parse_costs(c.as_text())
        counts = pc.counts_by_collective
        assert counts.get('all-reduce', 0) == 5, counts
        # each all-reduce moves the 1024-float local shard
        assert abs(pc.collective_bytes - 5 * 1024 * 4) < 1e-6, \\
            pc.bytes_by_collective
        print('OK')
    """, n_devices=8)


def test_sign_ef_collective_bytes_by_dtype(subproc):
    """Post-compression wire accounting: a sign-EF exchange's collective
    payload parses as int8 signs (1 byte/element — exactly the model's
    ``jit_wire_bytes_per_element``) plus a scalar f32 scale, so the HLO
    report and ``comm.choose``'s auto decision agree on bytes."""
    subproc("""
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch import hloparse
        from repro import comm
        from repro.core import compression
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((4,), ('pod',))
        plan = comm.make_plan('psum', 'sign_ef', n_total=4, axis_name='pod')
        n = 4096
        @partial(jax.shard_map, mesh=mesh, in_specs=(P('pod'), P('pod')),
                 out_specs=P('pod'), check_vma=False)
        def body(delta, ef):
            mean, _ = plan.reduce_mean_flat(delta, ef)
            return mean[None]
        x = jnp.ones((4, 1, n)); e = jnp.zeros((4, 1, n))
        c = jax.jit(body).lower(x, e).compile()
        pc = hloparse.parse_costs(c.as_text())
        by_dt = pc.collective_bytes_by_dtype
        assert by_dt.get('s8', 0) == n, by_dt        # signs: 1 byte/element
        assert 0 < by_dt.get('f32', 0) <= 64, by_dt  # the scalar scale
        model = plan.wire_bytes(n)                   # jit accounting
        assert abs(by_dt['s8'] - model) < 1, (by_dt, model)
        print('OK')
    """, n_devices=4)


def test_tensor_bytes_parsing():
    assert hloparse._tensor_bytes_public("f32[128,256]{1,0}") == 128 * 256 * 4
    assert hloparse._tensor_bytes_public(
        "(bf16[8]{0}, s32[2,2]{1,0})") == 16 + 16
    assert hloparse._tensor_bytes_public("pred[]") == 1
