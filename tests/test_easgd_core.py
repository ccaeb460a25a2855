"""EASGD update-rule invariants + packed/unpacked equivalence + compression.

Property tests (hypothesis) cover the algebraic identities the paper's
method relies on; exact-match tests pin the packed shard_map implementation
to the per-tensor reference. hypothesis is optional (requirements-dev.txt):
when absent the property tests are skipped and deterministic fallbacks
keep the invariants covered.
"""
import re
import warnings

warnings.filterwarnings("ignore")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # property tests skipped, fallbacks below
    given = settings = st = None

from repro.core import (
    EASGDConfig, ElasticConfig, Packer,
    elastic_apply_gradients, elastic_init,
)
from repro.core import compression, easgd
from repro.launch.mesh import make_host_mesh
from repro.core.elastic import n_pods_of


def tree_allclose(a, b, rtol=1e-5, atol=1e-6):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)


def _check_packer_roundtrip(tree, align=8):
    tree = {k: jnp.asarray(v) for k, v in tree.items()}
    pk = Packer(tree, align=align) if align is not None else Packer(tree)
    back = pk.unpack(pk.pack(tree))
    tree_allclose(tree, back)
    return pk


def _check_rho_zero_is_momentum_sgd(eta, mu):
    """ρ=0 degenerates eqs 5-6 to plain momentum SGD (eqs 3-4)."""
    cfg = EASGDConfig(eta=eta, rho=0.0, mu=mu)
    w = {"a": jnp.ones((3, 2))}
    v = {"a": jnp.zeros((3, 2))}
    g = {"a": jnp.full((3, 2), 0.3)}
    c = {"a": jnp.full((3, 2), 7.0)}   # center shouldn't matter at ρ=0
    w1, v1 = easgd.measgd_worker_update(w, v, g, c, cfg)
    w2, v2 = easgd.msgd_update(w, v, g, cfg)
    tree_allclose(w1, w2)
    tree_allclose(v1, v2)


if st is not None:

    @st.composite
    def small_tree(draw):
        n = draw(st.integers(1, 4))
        tree = {}
        for i in range(n):
            shape = tuple(draw(st.lists(st.integers(1, 5), min_size=0,
                                        max_size=3)))
            tree[f"p{i}"] = np.asarray(
                draw(st.lists(st.floats(-2, 2, width=32),
                              min_size=int(np.prod(shape) or 1),
                              max_size=int(np.prod(shape) or 1))),
                np.float32).reshape(shape)
        return tree

    @settings(max_examples=25, deadline=None)
    @given(small_tree())
    def test_packer_roundtrip(tree):
        _check_packer_roundtrip(tree)

    @settings(max_examples=20, deadline=None)
    @given(st.floats(0.001, 0.5), st.floats(0.0, 0.99))
    def test_rho_zero_is_momentum_sgd(eta, mu):
        _check_rho_zero_is_momentum_sgd(eta, mu)


def test_packer_roundtrip_deterministic():
    """hypothesis-free coverage of the roundtrip (incl. default alignment
    and scalar/empty-shape leaves)."""
    rng = np.random.RandomState(0)
    tree = {"w": rng.randn(3, 4).astype(np.float32),
            "b": rng.randn(7).astype(np.float32),
            "s": np.float32(1.5)}
    _check_packer_roundtrip(tree, align=8)
    # default alignment = the Pallas elastic-update tile (shared constant)
    from repro.core.packing import ELASTIC_UPDATE_BLOCK
    pk = _check_packer_roundtrip({"w": jnp.ones((5, 3))}, align=None)
    assert pk.align == ELASTIC_UPDATE_BLOCK
    assert pk.buffer_size == ELASTIC_UPDATE_BLOCK  # padded to one full tile


def test_rho_zero_is_momentum_sgd_deterministic():
    for eta, mu in ((0.01, 0.0), (0.1, 0.9), (0.5, 0.99)):
        _check_rho_zero_is_momentum_sgd(eta, mu)


def test_center_update_forms_agree():
    """Eq 2 via sum, via mean, and via P sequential single-worker updates
    agree (single-worker form composes only to first order — use the exact
    sum/mean pair)."""
    cfg = EASGDConfig(eta=0.1, rho=0.2)
    P_ = 4
    rng = np.random.RandomState(0)
    ws = [jnp.asarray(rng.randn(5), jnp.float32) for _ in range(P_)]
    center = jnp.asarray(rng.randn(5), jnp.float32)
    s = easgd.center_update_from_sum(center, sum(ws), P_, cfg)
    m = easgd.center_update_from_mean(center, sum(ws) / P_, P_, cfg)
    tree_allclose(s, m)


@pytest.mark.parametrize("compression_name", ["none", "bf16", "sign_ef"])
def test_packed_unpacked_equivalence(compression_name):
    """The packed shard_map exchange == per-tensor reference (exact for
    'none'; compression changes numerics by design, so only 'none' is
    exact)."""
    cfg_kw = dict(easgd=EASGDConfig(eta=0.05, rho=0.1, mu=0.9))
    params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 10,
              "b": jnp.ones((4,))}
    st_ = elastic_init(ElasticConfig(**cfg_kw), 0) if False else None
    cfg_u = ElasticConfig(packed=False, **cfg_kw)
    cfg_p = ElasticConfig(packed=True, compression=compression_name,
                          **cfg_kw)
    state = elastic_init(params, cfg_u, n_pods=2)
    grads = jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, 0.2).at[0].set(-0.1), state.params)
    mesh = make_host_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P
    pspecs = {"w": P(), "b": P()}
    out_u = elastic_apply_gradients(state, grads, cfg_u)
    state_p = elastic_init(params, cfg_p, n_pods=2)
    out_p = elastic_apply_gradients(state_p, grads, cfg_p, mesh=mesh,
                                    param_specs=pspecs, pod_axis=None)
    if compression_name == "none":
        tree_allclose(out_u.params, out_p.params)
        tree_allclose(out_u.center, out_p.center)
    else:
        # compressed exchange must still move the center toward the mean
        for k in params:
            assert np.all(np.isfinite(np.asarray(out_p.params[k])))


@pytest.mark.parametrize("n_pods,shapes,low", [
    (1, {"w": (3, 4), "b": (4,)}, False),          # the one-chip cells
    (2, {"a": (4101,), "b": (8, 128)}, False),     # odd leaf beside aligned
    (3, {"a": (4101,), "b": (8, 128)}, False),
    (2, {"a": (4101,), "b": (8, 128)}, True),      # bf16 momentum, center
])
def test_packed_exchange_matches_paper_rules(n_pods, shapes, low):
    """One step through the packed shard_map exchange against the paper's
    rules: eqs 5-6 (``measgd_worker_update``) per pod, then eq 2
    (``center_update_from_mean``) from the mean of the pre-update weights.
    With bfloat16 momentum and center, those two are compared within one
    bfloat16 rounding of the float32 rules."""
    from jax.sharding import PartitionSpec as P
    e = EASGDConfig(eta=0.1, rho=0.5, mu=0.9)
    dt = jnp.bfloat16 if low else jnp.float32
    cfg = ElasticConfig(easgd=e, packed=True, momentum_dtype=dt,
                        center_dtype=dt)
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 4 * len(shapes)))
    rand = lambda shape: jax.random.normal(next(keys), shape)
    params = {k: rand(s) for k, s in shapes.items()}
    state = elastic_init(params, cfg, n_pods=n_pods)
    state = state._replace(
        params={k: rand((n_pods,) + s) for k, s in shapes.items()},
        momentum={k: rand((n_pods,) + s).astype(dt)
                  for k, s in shapes.items()})
    grads = {k: rand((n_pods,) + s) for k, s in shapes.items()}
    mesh = make_host_mesh((1, 1), ("data", "model"))
    out = elastic_apply_gradients(state, grads, cfg, mesh=mesh,
                                  param_specs={k: P() for k in shapes},
                                  pod_axis=None)

    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t)
    center = f32(state.center)
    w_want, v_want = easgd.measgd_worker_update(
        state.params, f32(state.momentum), grads, center, e)
    mean_w = jax.tree_util.tree_map(lambda w: jnp.mean(w, axis=0),
                                    state.params)
    c_want = easgd.center_update_from_mean(center, mean_w, n_pods, e)
    assert int(out.step) == 1
    rtol = 2 ** -8 if low else 1e-5
    for name, got, want, dtype, tol in (
            ("w", out.params, w_want, jnp.float32, 1e-5),
            ("v", out.momentum, v_want, dt, rtol),
            ("c", out.center, c_want, dt, rtol)):
        for k in shapes:
            assert got[k].dtype == dtype, (name, k)
            np.testing.assert_allclose(
                np.asarray(got[k], np.float32), np.asarray(want[k]),
                rtol=tol, atol=1e-6, err_msg=f"{name}[{k}]")


def _flat_buffer_update(state, grads, e):
    """Oracle: the update as one flat-buffer body — pack W, G, V and W̄ into
    float32 buffers, run eqs 5-6 and 2 on them, slice W, V and W̄ back out
    at their dtypes. The per-leaf update must equal it bit for bit."""
    n_pods = n_pods_of(state)
    leaves = jax.tree_util.tree_leaves

    def pack(tree, pods):
        return jnp.concatenate(
            [l.reshape(pods, -1).astype(jnp.float32) for l in leaves(tree)],
            axis=1)

    def unpack(buf, template):
        out, off = [], 0
        for l in leaves(template):
            size = l.size // buf.shape[0]
            out.append(buf[:, off:off + size].reshape(l.shape)
                       .astype(l.dtype))
            off += size
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), out)

    w2 = pack(state.params, n_pods)
    g2 = pack(grads, n_pods)
    v2 = pack(state.momentum, n_pods)
    c2 = pack(state.center, 1)
    mean_w = c2[0] + jnp.sum(w2 - c2, axis=0) / float(n_pods)
    v_new = e.mu * v2 - e.eta * g2
    w_new = w2 + v_new - e.eta * e.rho * (w2 - c2)
    c_new = c2[0] + e.alpha * n_pods * (mean_w - c2[0])
    return (unpack(w_new, state.params), unpack(v_new, state.momentum),
            unpack(c_new[None], state.center))


@pytest.mark.parametrize("low", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("n_pods", [1, 2, 3])
def test_per_leaf_update_bitwise_matches_flat_buffers(n_pods, low):
    """The per-leaf update (packed=True, no pod axis) equals the flat-buffer
    arithmetic bit for bit on W, V and W̄: one leaf of 4101 elements beside
    lane-aligned ones, float32 and bfloat16 momentum and center."""
    from jax.sharding import PartitionSpec as P
    e = EASGDConfig(eta=0.1, rho=0.5, mu=0.9)
    dt = jnp.bfloat16 if low else jnp.float32
    cfg = ElasticConfig(easgd=e, packed=True, momentum_dtype=dt,
                        center_dtype=dt)
    shapes = {"a": (4101,), "b": (8, 128), "c": (2, 3, 128)}
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 5 * len(shapes)))
    rand = lambda shape: jax.random.normal(next(keys), shape)
    pod = lambda: {k: rand((n_pods,) + s) for k, s in shapes.items()}
    state = elastic_init({k: rand(s) for k, s in shapes.items()}, cfg,
                         n_pods=n_pods)
    state = state._replace(
        params=pod(),
        momentum={k: v.astype(dt) for k, v in pod().items()},
        center={k: rand(s).astype(dt) for k, s in shapes.items()})
    grads = pod()
    mesh = make_host_mesh((1, 1), ("data", "model"))
    out = jax.jit(lambda s, g: elastic_apply_gradients(
        s, g, cfg, mesh=mesh, param_specs={k: P() for k in shapes},
        pod_axis=None))(state, grads)
    want = jax.jit(lambda s, g: _flat_buffer_update(s, g, e))(state, grads)
    for name, got, ref in zip("wvc", (out.params, out.momentum, out.center),
                              want):
        for k in shapes:
            assert got[k].dtype == ref[k].dtype, (name, k)
            np.testing.assert_array_equal(
                np.asarray(got[k], np.float32),
                np.asarray(ref[k], np.float32), err_msg=f"{name}[{k}]")


@pytest.mark.parametrize("compression_name,packs",
                         [("none", False), ("sign_ef", True)])
def test_one_worker_update_packs_nothing(compression_name, packs):
    """One worker and no codec: the lowered update holds no concatenate and
    no float32 buffer of the model's element count. A codec encodes the flat
    buffer, so there the model's buffer is packed (the control)."""
    from jax.sharding import PartitionSpec as P
    cfg = ElasticConfig(easgd=EASGDConfig(eta=0.1, rho=0.5, mu=0.9),
                        packed=True, compression=compression_name)
    shapes = {"a": (4101,), "b": (8, 128)}
    n = sum(int(np.prod(s)) for s in shapes.values())
    state = elastic_init({k: jnp.ones(s) for k, s in shapes.items()}, cfg,
                         n_pods=1)
    mesh = make_host_mesh((1, 1), ("data", "model"))
    text = jax.jit(lambda s, g: elastic_apply_gradients(
        s, g, cfg, mesh=mesh, param_specs={k: P() for k in shapes},
        pod_axis=None)).lower(state, state.params).as_text()
    flat = re.search(rf"tensor<(1x)?{n}xf32>", text)
    assert ("concatenate" in text) == packs
    assert (flat is not None) == packs, flat


def test_tau_period():
    """τ=3: center only updates on steps 0, 3, 6, ..."""
    cfg = ElasticConfig(easgd=EASGDConfig(eta=0.1, rho=0.1, mu=0.0, tau=3),
                        packed=False)
    params = {"w": jnp.ones((2, 2))}
    state = elastic_init(params, cfg, n_pods=2)
    grads = {"w": jnp.stack([jnp.full((2, 2), 1.0),
                             jnp.full((2, 2), -0.4)])}
    centers = []
    for _ in range(6):
        state = elastic_apply_gradients(state, grads, cfg)
        centers.append(np.asarray(state.center["w"]).copy())
    # steps 1,2 (no exchange): center frozen; step 3 (step%3==0): moves
    assert np.allclose(centers[1], centers[0])
    assert np.allclose(centers[2], centers[1])
    assert not np.allclose(centers[3], centers[2])


def test_sign_ef_error_feedback_converges():
    """With error feedback, the compressed mean tracks the true mean: the
    accumulated EF error stays bounded while the center approaches the
    workers' mean."""
    cfg = ElasticConfig(easgd=EASGDConfig(eta=0.2, rho=0.5, mu=0.0),
                        packed=True, compression="sign_ef")
    params = {"w": jnp.zeros((16,))}
    state = elastic_init(params, cfg, n_pods=2)
    # workers pinned apart by antisymmetric gradients; center should stay ~0
    grads = {"w": jnp.stack([jnp.ones(16), -jnp.ones(16)])}
    mesh = make_host_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P
    for _ in range(10):
        state = elastic_apply_gradients(state, grads, cfg, mesh=mesh,
                                        param_specs={"w": P()},
                                        pod_axis=None)
    assert np.all(np.abs(np.asarray(state.center["w"])) < 1.0)
    assert np.all(np.isfinite(np.asarray(state.ef_error["w"])))


def test_consensus_contraction():
    """Pure elastic dynamics (zero grads): workers and center contract
    toward each other (the EASGD stability condition)."""
    cfg = ElasticConfig(easgd=EASGDConfig(eta=0.5, rho=0.5, mu=0.0),
                        packed=False)
    params = {"w": jnp.zeros((8,))}
    state = elastic_init(params, cfg, n_pods=3)
    # spread the workers out
    spread = jnp.stack([jnp.full((8,), -1.0), jnp.zeros((8,)),
                        jnp.full((8,), 1.0)])
    state = state._replace(params={"w": spread})
    zeros = {"w": jnp.zeros_like(spread)}
    def spread_of(s):
        return float(jnp.max(jnp.abs(
            s.params["w"] - s.center["w"][None])))
    s0 = spread_of(state)
    for _ in range(5):
        state = elastic_apply_gradients(state, zeros, cfg)
    assert spread_of(state) < s0
