"""Substrate subsystems: data pipeline, checkpointing, fault tolerance,
optimizers, async engine, DES, cost model."""
import warnings

warnings.filterwarnings("ignore")

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # property tests skipped, fallback below
    given = settings = st = None

from repro.core import costmodel
from repro.core.async_engine import ALGORITHMS, PSEngine, SimConfig
from repro.core.easgd import EASGDConfig
from repro.core.elastic import ElasticConfig
from repro.core import elastic
from repro.checkpoint import CheckpointManager
from repro.data import ShardedPipeline, SyntheticLMStream
from repro.ft import BoundedStaleness, Watchdog, pod_join, pod_leave, \
    rescale_pods
from repro import optim


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def _factory(shard, n_shards):
    return SyntheticLMStream(vocab_size=97, seq=16, batch=4, seed=7,
                             shard=shard, n_shards=n_shards)


def test_pipeline_deterministic_and_resumable():
    p1 = ShardedPipeline(_factory, n_pods=2)
    a = [p1.next() for _ in range(3)]
    p2 = ShardedPipeline(_factory, n_pods=2)
    b = [p2.next() for _ in range(3)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    # resume from step 1 reproduces batch 1
    p2.restore(1)
    again = p2.next()
    np.testing.assert_array_equal(a[1]["tokens"], again["tokens"])


def test_pipeline_prefetch_matches_sync():
    ps = ShardedPipeline(_factory, n_pods=1)
    sync = [ps.next() for _ in range(4)]
    pa = ShardedPipeline(_factory, n_pods=1).start()
    try:
        async_ = [pa.next() for _ in range(4)]
    finally:
        pa.stop()
    for x, y in zip(sync, async_):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])


def test_pipeline_spans_on_the_profiler_trace(tmp_path):
    """``repro.input.produce`` around each batch's generation on the
    producer thread; ``repro.input.wait`` only around a ``next()`` that
    found the prefetch queue empty."""
    import glob
    import time

    from jax.profiler import ProfileData

    class Slow:
        def __init__(self, shard, n_shards):
            pass

        def batch_at(self, step):
            time.sleep(0.05)
            return {"tokens": np.full((2, 4), step)}

    pipe = ShardedPipeline(Slow, n_pods=2)
    with jax.profiler.trace(str(tmp_path)):
        pipe.start()
        first = pipe.next()          # nothing made yet: waits
        deadline = time.time() + 10
        while pipe._q.qsize() < pipe.depth and time.time() < deadline:
            time.sleep(0.01)
        later = [pipe.next() for _ in range(pipe.depth)]   # queue full
        pipe.stop()
    assert [b["tokens"][0, 0, 0] for b in [first] + later] == [0, 1, 2]
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.duration_ns) for plane in
              ProfileData.from_file(path[0]).planes for line in plane.lines
              for e in line.events if e.name.startswith("repro.")]
    names = [n for n, _ in events]
    assert names.count("repro.input.wait") == 1, names
    assert names.count("repro.input.produce") >= 3, names
    # each produce span holds two 50 ms shards
    assert all(d >= 0.1e9 for n, d in events
               if n == "repro.input.produce"), events


def test_pipeline_shards_disjoint():
    p = ShardedPipeline(_factory, n_pods=2)
    b = p.next()
    assert b["tokens"].shape[0] == 2
    assert not np.array_equal(b["tokens"][0], b["tokens"][1])


def test_lm_stream_learnable_structure():
    """Next token is (mostly) an affine function of the current one — a
    bigram table gets well below uniform entropy accuracy."""
    s = SyntheticLMStream(vocab_size=31, seq=64, batch=32, seed=0)
    b = s.batch_at(0)
    t, tgt = b["tokens"], b["targets"]
    pred = (31 % 31 + 31) and ((t * (31 % 31 or 1)))  # noqa - see below
    # empirical: P(target == (a*t+7+i%5) mod V) must dominate chance
    hits = 0
    total = 0
    for i in range(63):
        want = (31 % 31 or 1)
        nxt = (31 * t[:, i] + 7 + ((i + 1) % 5)) % 31
        hits += np.sum(tgt[:, i] == t[:, i + 1])
        total += t.shape[0]
    assert hits / total == 1.0     # targets are the shifted tokens


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    cfg = ElasticConfig(easgd=EASGDConfig())
    state = elastic.init({"w": jnp.arange(6.0).reshape(2, 3)}, cfg, n_pods=2)
    mgr.save(3, state, extra={"data_step": 3})
    mgr.save(7, state._replace(step=jnp.asarray(7)), extra={"data_step": 7})
    assert mgr.all_steps() == [3, 7]
    restored, meta = mgr.restore(state)
    assert meta["extra"]["data_step"] == 7
    assert int(restored.step) == 7
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.asarray(state.params["w"]))


def test_checkpoint_keep_n(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"w": jnp.ones((2,))}
    for s in (1, 2, 3, 4):
        mgr.save(s, state)
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = {"w": jnp.ones((4,))}
    mgr.save_async(5, state)
    mgr.wait()
    restored, _ = mgr.restore(state)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.ones((4,)))


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.ones((4,))})
    with pytest.raises(AssertionError):
        mgr.restore({"w": jnp.ones((5,))})


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_pod_join_seeds_from_center():
    cfg = ElasticConfig(easgd=EASGDConfig())
    state = elastic.init({"w": jnp.full((3,), 2.0)}, cfg, n_pods=2)
    state = state._replace(center={"w": jnp.full((3,), 5.0)})
    grown = pod_join(state)
    assert grown.params["w"].shape[0] == 3
    np.testing.assert_allclose(np.asarray(grown.params["w"][2]), 5.0)
    np.testing.assert_allclose(np.asarray(grown.momentum["w"][2]), 0.0)


def test_pod_leave_and_rescale():
    cfg = ElasticConfig(easgd=EASGDConfig())
    state = elastic.init({"w": jnp.ones((3,))}, cfg, n_pods=4)
    marked = state.params["w"].at[2].set(9.0)
    state = state._replace(params={"w": marked})
    st2 = pod_leave(state, 2)
    assert st2.params["w"].shape[0] == 3
    assert not np.any(np.asarray(st2.params["w"]) == 9.0)
    st3 = rescale_pods(state, 6)
    assert st3.params["w"].shape[0] == 6
    # training continues after rescale
    grads = {"w": jnp.ones((6, 3))}
    out = elastic.apply_gradients(st3, grads, cfg)
    assert int(out.step) == 1


def test_bounded_staleness_mask():
    pol = BoundedStaleness(n_pods=8, deadline_factor=1.5)
    delays = [1, 1, 1, 1, 1, 1, 1, 10.0]
    mask = pol.participation(0, delays)
    assert mask.sum() == 7 and mask[-1] == 0
    # quorum guard
    pol2 = BoundedStaleness(n_pods=4, deadline_factor=0.01, min_quorum=0.5)
    mask2 = pol2.participation(0, [1.0, 1.1, 1.2, 1.3])
    assert mask2.sum() >= 2


def test_watchdog_heartbeat_and_stop(tmp_path):
    hb = str(tmp_path / "hb")
    wd = Watchdog(heartbeat_path=hb, interval_s=0.05,
                  install_signals=False).start_heartbeat()
    import time
    time.sleep(0.15)
    assert Watchdog.is_alive(hb, timeout_s=5)
    wd.should_stop.set()
    wd.close()


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def test_momentum_sgd_matches_easgd_rho0():
    init, update = optim.momentum_sgd(lr=0.1, mu=0.9)
    params = {"w": jnp.ones((3,))}
    st = init(params)
    g = {"w": jnp.full((3,), 0.5)}
    p1, st = update(g, st, params)
    # hand-check: v = -0.05, w = 0.95
    np.testing.assert_allclose(np.asarray(p1["w"]), 0.95)


def test_adam_step_decreases_quadratic():
    init, update = optim.adam(lr=0.1)
    w = {"w": jnp.asarray([3.0, -2.0])}
    st = init(w)
    for _ in range(50):
        g = {"w": 2 * w["w"]}
        w, st = update(g, st, w)
    assert float(jnp.sum(jnp.square(w["w"]))) < 1.0


def test_schedules():
    s = optim.linear_warmup_cosine(1.0, warmup=10, total=100)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1.0) < 1e-6
    assert float(s(100)) < 0.01
    sd = optim.step_decay(1.0, 0.5, 10)
    assert abs(float(sd(25)) - 0.25) < 1e-6


# ---------------------------------------------------------------------------
# async engine + cost model
# ---------------------------------------------------------------------------

def _tiny_engine(seed=0):
    rng = np.random.RandomState(seed)
    A = rng.randn(8, 8).astype(np.float64)
    A = A @ A.T / 8 + np.eye(8)          # SPD quadratic
    w_star = rng.randn(8)

    def grad_fn(w, step, worker):
        noise = np.random.RandomState(step * 131 + worker).randn(8) * 0.1
        return A @ (w - w_star) + noise

    def err_fn(w):
        return float(np.linalg.norm(w - w_star))

    # eta small enough that master-side momentum (async_msgd) stays stable
    # on this quadratic (the paper's Fig 6.2 shows MSGD's instability at
    # higher rates — MEASGD is the fix)
    return PSEngine(grad_fn, err_fn, np.zeros(8),
                    EASGDConfig(eta=0.015, rho=0.05, mu=0.9),
                    SimConfig(n_workers=4, t_compute=1e-3, seed=seed))


@pytest.mark.parametrize("algo", [a for a in ALGORITHMS
                                  if a != "async_msgd"])
def test_async_engine_runs_and_converges(algo):
    eng = _tiny_engine()
    res = eng.run(algo, total_iters=600)
    assert res.total_iters >= 600 or res.total_time_s > 0
    assert res.final_metric < 2.0          # moved toward w*
    assert 0 <= res.breakdown["fwd_bwd"]


def test_measgd_more_stable_than_msgd():
    """Paper Fig 6.2: worker-side momentum (MEASGD) is stable where
    master-side momentum (MSGD) compounds with asynchrony-induced implicit
    momentum and diverges."""
    msgd = _tiny_engine(0).run("async_msgd", total_iters=600)
    measgd = _tiny_engine(0).run("async_measgd", total_iters=600)
    assert measgd.final_metric < 2.0
    assert measgd.final_metric < msgd.final_metric


def test_async_engine_deterministic():
    r1 = _tiny_engine(3).run("hogwild_easgd", total_iters=300)
    r2 = _tiny_engine(3).run("hogwild_easgd", total_iters=300)
    assert r1.history == r2.history


def test_sync_easgd_faster_than_original():
    """The paper's headline ordering, on modeled time at equal iterations."""
    e1 = _tiny_engine(1)
    sync = e1.run("sync_easgd", total_iters=1000)
    orig = _tiny_engine(1).run("original_easgd", total_iters=1000)
    assert sync.total_time_s < orig.total_time_s


def test_costmodel_packed_beats_unpacked():
    sizes = [4_000] * 50
    for net in (costmodel.MELLANOX_FDR, costmodel.TPU_ICI):
        assert costmodel.t_packed(sizes, 16, net) < \
            costmodel.t_per_layer(sizes, 16, net)


def _check_tree_vs_roundrobin(p, nbytes):
    """Θ(log P) tree beats the Θ(P) round-robin for P ≥ 6. (Not P ≥ 4: at
    P=5 the two-phase tree's 2·⌈log2 5⌉ = 6 rounds lose to 5 serialized
    messages when latency dominates — 2·⌈log2 P⌉ ≤ P holds from P=6 up.)"""
    net = costmodel.MELLANOX_FDR
    if p >= 6:
        assert costmodel.t_tree_allreduce(nbytes, p, net) <= \
            costmodel.t_round_robin(nbytes, p, net)


if st is not None:

    @settings(max_examples=30, deadline=None)
    @given(st.integers(2, 512), st.floats(1e3, 1e9))
    def test_costmodel_tree_vs_roundrobin(p, nbytes):
        _check_tree_vs_roundrobin(p, nbytes)


def test_costmodel_tree_vs_roundrobin_deterministic():
    for p in (6, 7, 16, 511, 512):
        for nbytes in (1e3, 1e6, 1e9):
            _check_tree_vs_roundrobin(p, nbytes)
