"""The bucketed overlap data plane (ISSUE 6), bottom-up.

 1. Boundary policy: cuts land on layer edges, respect the target size,
    and the jax-free align constant cannot drift from the packer's.
 2. Bitwise law: the bucketed exchange is a VIEW of the monolithic
    schedule — same final weights, bit for bit, on the thread transport
    (sync_easgd/sync_sgd × ring/tree × P∈{2,3,4}) and through the real
    TCP p2p wire (overlap on and off).
 3. Accounting: per-bucket mesh byte counters partition the registry's
    ``bytes_from_rounds`` total exactly; schedule-level counters are
    identical with and without bucketing.
"""
import threading

import numpy as np
import pytest

from repro import ps
from repro.comm import rounds as comm_rounds
from repro.core.easgd import EASGDConfig

CFG = EASGDConfig(eta=0.05, rho=0.07, mu=0.9)


# ---------------------------------------------------------------------------
# (1) boundary policy
# ---------------------------------------------------------------------------

def test_elastic_align_constant_pins_packer_block():
    """rounds.ELASTIC_UPDATE_ALIGN is the jax-free copy of the packer's
    kernel tile — the two constants must never drift."""
    from repro.core.packing import ELASTIC_UPDATE_BLOCK
    assert comm_rounds.ELASTIC_UPDATE_ALIGN == ELASTIC_UPDATE_BLOCK


def test_bucket_boundaries_cut_at_layer_edges():
    sizes = [1024, 32, 128, 4]
    b = comm_rounds.bucket_boundaries(sizes, 1188, 32)
    assert b == [0, 1024, 1056, 1184, 1188]
    # target bigger than any layer group -> single bucket
    assert comm_rounds.bucket_boundaries(sizes, 1188, 10**6) == [0, 1188]
    # no layer structure -> uniform slabs
    assert comm_rounds.bucket_boundaries(None, 10, 4) == [0, 4, 8, 10]
    # align rounds cuts UP and drops colliding ones
    b = comm_rounds.bucket_boundaries([100, 100, 100], 300, 100, align=128)
    assert b[0] == 0 and b[-1] == 300
    assert all(c % 128 == 0 for c in b[1:-1])


def test_default_boundaries_align_only_at_block_scale():
    align = comm_rounds.ELASTIC_UPDATE_ALIGN
    # small buckets (tests, tiny problems): cut exactly at layer edges
    assert comm_rounds.default_bucket_boundaries(
        [100, 100, 100], 300, 800) == [0, 100, 200, 300]
    # block-scale buckets: interior cuts are kernel-tile aligned
    sizes = [align + 7, align - 3, 2 * align]
    n = sum(sizes) + 5
    b = comm_rounds.default_bucket_boundaries(sizes, n, align * 8)
    assert all(c % align == 0 for c in b[1:-1])


def test_bucket_rounds_partition_every_span():
    """Clipped spans across buckets reassemble each message's monolithic
    span exactly — nothing lost, nothing duplicated, order preserved."""
    P, n = 4, 1000
    rounds = comm_rounds.ring_rounds(P)
    bounds = comm_rounds.bucket_boundaries(None, n, 130)
    plans = comm_rounds.bucket_rounds(rounds, n, bounds)
    assert len(plans) == len(bounds) - 1
    for r_idx, rnd in enumerate(rounds):
        for m in rnd:
            a, b = m.span(n)
            got = sorted(
                span for plan in plans
                for mm, span in plan[r_idx] if mm is m)
            assert got[0][0] == a and got[-1][1] == b
            for (_, e0), (s1, _) in zip(got[:-1], got[1:]):
                assert e0 == s1          # contiguous, non-overlapping


# ---------------------------------------------------------------------------
# (2) the bitwise law
# ---------------------------------------------------------------------------

def _thread_run(algo, P, schedule, bucket_bytes, iters=36):
    cfg = ps.PSConfig(algorithm=algo, n_workers=P, total_iters=iters,
                      transport="thread", schedule=schedule,
                      eval_every_iters=10**9, bucket_bytes=bucket_bytes)
    return ps.run_ps(ps.NUMPY_MLP, CFG, cfg)


@pytest.mark.parametrize("algo", ["sync_easgd", "sync_sgd"])
@pytest.mark.parametrize("schedule,P", [
    ("ring", 2), ("ring", 3), ("ring", 4),   # ring takes any P
    ("tree", 2), ("tree", 4),                # tree is power-of-two only
])
def test_bucketed_bitwise_vs_monolithic_thread(algo, schedule, P):
    """Bucketing is a view, not a re-chunking: same final center and
    worker weights, bit for bit, and the schedule-level counters do not
    even notice (one exchange costs the same sync_rounds/messages/
    wire_bytes either way)."""
    mono = _thread_run(algo, P, schedule, bucket_bytes=0)
    bucketed = _thread_run(algo, P, schedule, bucket_bytes=256)
    np.testing.assert_array_equal(mono.center, bucketed.center)
    np.testing.assert_array_equal(mono.workers, bucketed.workers)
    for key in ("sync_rounds", "messages", "wire_bytes"):
        assert mono.counters[key] == bucketed.counters[key], key


@pytest.mark.parametrize("overlap", [True, False])
def test_bucketed_bitwise_through_tcp_p2p_wire(overlap):
    """The real thing: a bucketed, (optionally) overlapped TCP p2p run
    lands on exactly the bits of the monolithic thread run — streaming
    the row as per-layer SEGMENT buckets while compute proceeds moves
    time, never math. The BYE-folded overlap counters must exist and the
    comm clock must be positive."""
    P, iters = 3, 36
    mono = _thread_run("sync_easgd", P, "ring", bucket_bytes=0, iters=iters)
    cfg = ps.PSConfig(algorithm="sync_easgd", n_workers=P,
                      total_iters=iters, transport="tcp", schedule="ring",
                      sync_plane="p2p", eval_every_iters=10**9,
                      bucket_bytes=256, overlap=overlap)
    p2p = ps.run_ps(ps.NUMPY_MLP, CFG, cfg)
    np.testing.assert_array_equal(mono.center, p2p.center)
    np.testing.assert_array_equal(mono.workers, p2p.workers)
    assert p2p.counters["n_buckets"] > 1
    assert p2p.counters["comm_s"] > 0.0
    if not overlap:
        # inline exchange: everything the comm clock saw was exposed
        assert p2p.counters["overlapped_s"] == 0.0


# ---------------------------------------------------------------------------
# (3) accounting
# ---------------------------------------------------------------------------

def test_per_bucket_byte_counters_partition_registry_total():
    """Σ_workers bucket_send_bytes[b] == the registry's bytes_from_rounds
    clipped to bucket b — and summing over buckets recovers the monolithic
    total exactly (clipping partitions every span)."""
    from repro.comm.rounds import peer_pairs, ring_rounds
    from repro.net.peer import PeerMesh

    P, n = 3, 999
    rounds = ring_rounds(P)
    bounds = comm_rounds.bucket_boundaries(None, n, 250)
    meshes = [PeerMesh(w, "t", bind_host="127.0.0.1", timeout_s=30)
              for w in range(P)]
    directory = {w: ("127.0.0.1", m.port) for w, m in enumerate(meshes)}
    rows = [np.arange(n) * (w + 1.0) for w in range(P)]
    errs, threads = [], []

    def _run(wid):
        try:
            meshes[wid].connect(directory, peer_pairs(rounds))
            meshes[wid].set_rounds(rounds, n, boundaries=bounds)
            meshes[wid].execute_exchange(rows[wid])
        except BaseException as e:          # noqa: BLE001
            errs.append(e)

    for wid in range(P):
        threads.append(threading.Thread(target=_run, args=(wid,)))
        threads[-1].start()
    for th in threads:
        th.join(timeout=60)
    for m in meshes:
        m.close()
    assert not errs, errs
    want = rows[0] * 0 + sum(np.arange(n) * (w + 1.0) for w in range(P))
    for row in rows:
        np.testing.assert_array_equal(row, want)

    measured = np.zeros(len(bounds) - 1, dtype=np.int64)
    for m in meshes:
        measured += np.asarray(m.bucket_send_bytes, np.int64)
    predicted = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        tot = 0
        for rnd in rounds:
            for msg in rnd:
                span = comm_rounds.clip_span(msg, n, lo, hi)
                if span is not None:
                    tot += (span[1] - span[0]) * 8
        predicted.append(tot)
    assert list(measured) == predicted
    assert int(measured.sum()) == int(
        comm_rounds.bytes_from_rounds(rounds, n * 8))


# ---------------------------------------------------------------------------
# the zoo rides the same rails
# ---------------------------------------------------------------------------

def test_zoo_layer_sizes_drive_boundaries():
    """Every zoo problem advertises its layer structure, and the runtime's
    boundary policy cuts the padded row on it."""
    from repro.ps import zoo
    w0, grad_fn, _ = ps.NUMPY_MLP.build()
    assert sum(grad_fn.layer_sizes) == w0.size
    b = comm_rounds.default_bucket_boundaries(grad_fn.layer_sizes,
                                              w0.size, 2048)
    assert b[0] == 0 and b[-1] == w0.size and len(b) > 2
    assert "gemma3-27b" in zoo.zoo_names()
    spec = zoo.resolve("gemma3-27b")
    assert spec.factory == "repro.ps.zoo:make_zoo_lm"
