"""The exchange-schedule registry: ONE definition per schedule, carrying BOTH
the runnable ``shard_map`` implementation AND the α–β cost function.

This is the single source of truth for how cross-pod exchanges move bytes.
A schedule registered here is simultaneously

 * **runnable**  — ``Schedule.allreduce(x, axis_name)`` inside ``shard_map``
   on a real mesh (``core.elastic`` / ``runtime.train`` consume it through an
   ``ExchangePlan``, see ``repro.comm.plan``),
 * **simulatable** — ``Schedule.cost(n_bytes, p, net)`` prices the same
   exchange under the paper's α–β model (``core.async_engine`` and
   ``core.des`` charge their discrete-event clocks through it), and
 * **benchmarkable** — the table3/table4 sweeps iterate ``names()``.

Paper mapping (§5.1/§6.1): Original EASGD's round-robin master↔worker
exchange is Θ(P) serialized messages; the paper's fix is a tree reduction
Θ(log P). ``ring`` is the bandwidth-optimal schedule a tuned library picks
for large buffers; ``psum`` is XLA's native all-reduce (priced as the best
of butterfly/ring — what a tuned library achieves).

All implementations compute the global **sum** over the bound mesh axis,
exactly like ``lax.psum`` — equivalence is pinned by tests on host meshes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import costmodel
from repro.comm.rounds import (       # noqa: F401 — re-exported: one
    MASTER,                           # definition of the round structure,
    Message,                          # importable jax-free from comm.rounds
    _inner_size,                      # (the TCP workers' p2p data plane)
    butterfly_rounds,
    bytes_from_rounds,
    hierarchical_rounds,
    peer_pairs,
    psum_rounds,
    ring_rounds,
    round_robin_rounds,
    rounds_from_wire,
    rounds_to_wire,
    t_rounds,
    t_rounds_buckets,
    topology_group,
    tree_rounds,
)


# ---------------------------------------------------------------------------
# shard_map implementations (call INSIDE shard_map with the axis name bound)
# ---------------------------------------------------------------------------

def psum_allreduce(x, axis_name):
    """Baseline: XLA-native all-reduce."""
    return lax.psum(x, axis_name)


def tree_allreduce(x, axis_name):
    """Binomial-tree reduce-to-root + broadcast: 2·⌈log2 P⌉ rounds.

    The paper's §5.1 'tree reduction' in its literal two-phase form (the
    BCube/master-rooted variant). Requires a power-of-two axis size.
    """
    p = lax.axis_size(axis_name)
    assert p & (p - 1) == 0, f"tree needs power-of-two axis, got {p}"
    if p == 1:
        return x
    r = lax.axis_index(axis_name)
    # reduce phase: rank i+d sends its partial sum to rank i
    d = 1
    while d < p:
        perm = [(i + d, i) for i in range(0, p, 2 * d)]
        recv = lax.ppermute(x, axis_name, perm)  # non-receivers get zeros
        x = x + recv
        d *= 2
    # broadcast phase: mirror the tree back down from rank 0
    d = p // 2
    while d >= 1:
        perm = [(i, i + d) for i in range(0, p, 2 * d)]
        recv = lax.ppermute(x, axis_name, perm)
        x = jnp.where(r % (2 * d) == d, recv, x)
        d //= 2
    return x


def butterfly_allreduce(x, axis_name):
    """Recursive-doubling all-reduce: ⌈log2 P⌉ rounds, XOR partners.

    The Θ(log P) schedule of Sync EASGD without the separate broadcast
    phase. Requires a power-of-two axis size.
    """
    p = lax.axis_size(axis_name)
    assert p & (p - 1) == 0, f"butterfly needs power-of-two axis, got {p}"
    d = 1
    while d < p:
        perm = [(i, i ^ d) for i in range(p)]
        x = x + lax.ppermute(x, axis_name, perm)
        d *= 2
    return x


def ring_allreduce(x, axis_name):
    """Bandwidth-optimal ring all-reduce: reduce-scatter + all-gather.

    2(P−1) steps of (n/P)-byte messages. ``x`` must be 1-D (the registry's
    ``allreduce`` wrapper flattens automatically). Chunks are kept as
    (rows, 128) so the TPU tiles them lane-dense: a (P, n/P) buffer pads P
    up to 8 sublanes, and at n = 3e8 its step took 7 minutes to compile.
    """
    p = lax.axis_size(axis_name)
    if p == 1:
        return x
    r = lax.axis_index(axis_name)
    n = x.shape[0]
    pad = (-n) % (p * 128)
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    chunks = x.reshape(p, -1, 128)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def rs_step(s, ch):
        send = jax.lax.dynamic_index_in_dim(ch, (r - s) % p, 0, keepdims=False)
        recv = lax.ppermute(send, axis_name, perm)
        return ch.at[(r - s - 1) % p].add(recv)

    chunks = lax.fori_loop(0, p - 1, rs_step, chunks)
    # rank r now holds the fully-reduced chunk (r+1) mod p

    def ag_step(s, ch):
        send = jax.lax.dynamic_index_in_dim(ch, (r + 1 - s) % p, 0,
                                            keepdims=False)
        recv = lax.ppermute(send, axis_name, perm)
        return ch.at[(r - s) % p].set(recv)

    chunks = lax.fori_loop(0, p - 1, ag_step, chunks)
    out = chunks.reshape(-1)
    return out[:n] if pad else out


def _grouped_ring(x, axis_name, p, m, r):
    """Ring reduce-scatter + all-gather WITHIN groups of ``m`` consecutive
    ranks (all groups in parallel). 1-D x; requires m | p."""
    n = x.shape[0]
    pad = (-n) % m
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    chunks = x.reshape(m, -1)
    r_in = r % m
    perm = [(g * m + j, g * m + (j + 1) % m)
            for g in range(p // m) for j in range(m)]

    def rs_step(s, ch):
        send = jax.lax.dynamic_index_in_dim(ch, (r_in - s) % m, 0,
                                            keepdims=False)
        recv = lax.ppermute(send, axis_name, perm)
        return ch.at[(r_in - s - 1) % m].add(recv)

    chunks = lax.fori_loop(0, m - 1, rs_step, chunks)

    def ag_step(s, ch):
        send = jax.lax.dynamic_index_in_dim(ch, (r_in + 1 - s) % m, 0,
                                            keepdims=False)
        recv = lax.ppermute(send, axis_name, perm)
        return ch.at[(r_in - s) % m].set(recv)

    chunks = lax.fori_loop(0, m - 1, ag_step, chunks)
    out = chunks.reshape(-1)
    return out[:n] if pad else out


def hierarchical_grouped_allreduce(x, axis_name):
    """Two-level all-reduce on ONE axis (paper §6.2 made first-class):
    bandwidth-optimal ring within groups of ``inner`` consecutive ranks
    (the fast ICI domain), then latency-optimal butterfly across groups
    (the slow DCI domain) — the cross-group message count is 1/inner of a
    flat exchange. Requires a power-of-two axis size; 1-D x.

    ``hierarchical_allreduce`` below is the two-axis form for meshes that
    expose the pod/ICI split explicitly.
    """
    p = lax.axis_size(axis_name)
    assert p & (p - 1) == 0, f"hierarchical needs power-of-two axis, got {p}"
    if p == 1:
        return x
    r = lax.axis_index(axis_name)
    m = _inner_size(p)
    if m > 1:
        x = _grouped_ring(x, axis_name, p, m, r)
    d = m
    while d < p:
        perm = [(i, i ^ d) for i in range(p)]
        x = x + lax.ppermute(x, axis_name, perm)
        d *= 2
    return x


def round_robin_allreduce(x, axis_name):
    """The Original-EASGD wire schedule: the master (rank 0) exchanges with
    workers ONE AT A TIME, in rank order — Θ(P) serialized messages.

    Kept as the paper-faithful *baseline* schedule (this is intentionally
    the slow one). Semantics here: global sum, like the others, so
    correctness tests can compare directly.
    """
    p = lax.axis_size(axis_name)
    if p == 1:
        return x
    r = lax.axis_index(axis_name)
    acc = x
    # gather phase: worker i -> master, sequentially (i = 1..P-1)
    for i in range(1, p):
        recv = lax.ppermute(x, axis_name, [(i, 0)])
        acc = jnp.where(r == 0, acc + recv, acc)
    # broadcast phase: master -> worker i, sequentially
    out = acc
    for i in range(1, p):
        recv = lax.ppermute(acc, axis_name, [(0, i)])
        out = jnp.where(r == i, recv, out)
    return out


# ---------------------------------------------------------------------------
# round structure — the wire pattern as DATA
# ---------------------------------------------------------------------------
#
# The round structure itself lives in ``repro.comm.rounds`` (jax-free:
# the repro.net TCP workers execute it over direct worker↔worker links
# without importing this module) and is re-exported above. The α–β cost of
# a round is α + max_frac·n·β, and summing rounds reproduces the
# closed-form ``cost_fn`` exactly (pinned by tests) — while the repro.ps
# runtime EXECUTES the same rounds over its transports, so the real system
# and the simulator move the identical message pattern.


def t_hierarchical_allreduce(n: float, p: int, net: costmodel.Network
                             ) -> float:
    """ring over the inner group + butterfly across groups (paper §6.2)."""
    m = _inner_size(p)
    return (costmodel.t_ring_allreduce(n, m, net)
            + costmodel.t_butterfly_allreduce(n, max(p // m, 1), net))


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Schedule:
    """One exchange schedule = runnable implementation + α–β cost model.

    ``impl(x, axis_name)`` — global sum over the bound mesh axis, called
    inside ``shard_map`` (use ``allreduce`` which handles flattening).
    ``cost_fn(n_bytes, p, net)`` — seconds for one full group exchange of
    an n-byte buffer among p participants over ``net`` (α–β model).
    """

    name: str
    impl: Callable
    cost_fn: Callable
    flat_only: bool = False     # impl requires a 1-D buffer
    pow2_only: bool = False     # impl requires a power-of-two axis size
    rounds_fn: Callable | None = None   # (p, n_bytes, net) -> [[Message]]
    doc: str = ""

    def allreduce(self, x, axis_name: str):
        """Sum ``x`` over the mesh axis; flattens/reshapes for flat-only
        schedules so callers can pass any shape (scalars included)."""
        if self.flat_only and x.ndim != 1:
            return self.impl(x.reshape(-1), axis_name).reshape(x.shape)
        return self.impl(x, axis_name)

    def cost(self, n_bytes: float, p: int,
             net: costmodel.Network = costmodel.TPU_ICI) -> float:
        """α–β time of one full group exchange (0 for a single participant)."""
        if p <= 1:
            return 0.0
        return self.cost_fn(n_bytes, p, net)

    def rounds(self, p: int, n_bytes: float = 0.0,
               net: costmodel.Network = costmodel.TPU_ICI,
               topology: costmodel.Topology | None = None) -> list:
        """The exchange as explicit message rounds (empty for p ≤ 1).

        The repro.ps runtime executes exactly these over its transports;
        ``cost_from_rounds`` prices them and equals ``cost`` (pinned by
        tests) — one structure, run AND simulated. A ``topology`` shapes
        topology-aware builders (hierarchical groups by host) and lifts
        the flat pow2 gate for hierarchical — any p with a power-of-two
        GROUP count resolves there; the builder itself rejects the rest.
        """
        if p <= 1 or self.rounds_fn is None:
            return []
        if self.pow2_only and p & (p - 1) != 0 and not (
                self.name == "hierarchical" and topology is not None):
            raise ValueError(
                f"schedule '{self.name}' needs a power-of-two participant "
                f"count, got {p} — its round structure would address "
                f"nonexistent ranks (use ring/round_robin instead)")
        if topology is not None:
            return self.rounds_fn(p, n_bytes, net, topology=topology)
        return self.rounds_fn(p, n_bytes, net)

    def cost_from_rounds(self, n_bytes: float, p: int,
                         net: costmodel.Network = costmodel.TPU_ICI
                         ) -> float:
        """Per-round α–β pricing: each round costs α + max_frac·n·β (its
        messages fly concurrently); rounds are serialized. The uniform case
        of ``comm.rounds.t_rounds``, so there is one pricer."""
        return t_rounds(self.rounds(p, n_bytes, net), n_bytes, net=net)

    def cost_topo(self, n_bytes: float, p: int,
                  topology: costmodel.Topology | None = None) -> float:
        """α–β time of one exchange on a two-level fabric: the schedule's
        own rounds, priced message-by-message over the topology's link
        classes (``comm.rounds.t_rounds``). A missing or uniform topology
        degrades to the closed-form ``cost`` on the intra network — same
        floats, so homogeneous callers stay bitwise-equal to today."""
        if topology is None or topology.uniform:
            net = topology.intra if topology is not None else \
                costmodel.TPU_ICI
            return self.cost(n_bytes, p, net)
        if p <= 1:
            return 0.0
        return t_rounds(
            self.rounds(p, n_bytes, topology.intra, topology=topology),
            n_bytes, net=topology.intra, topology=topology)

    def bytes_from_rounds(self, n_bytes: float, p: int,
                          net: costmodel.Network = costmodel.TPU_ICI
                          ) -> float:
        """TOTAL payload bytes the schedule's messages move for one
        exchange of an n-byte buffer (every message counted — this is what
        the p2p data plane's measured per-link byte counters must sum to;
        ``cost_from_rounds`` prices the same structure in time)."""
        return bytes_from_rounds(self.rounds(p, n_bytes, net), n_bytes)


SCHEDULES: dict[str, Schedule] = {}


def register(schedule: Schedule) -> Schedule:
    SCHEDULES[schedule.name] = schedule
    return schedule


def get(name: str) -> Schedule:
    try:
        return SCHEDULES[name]
    except KeyError:
        raise ValueError(
            f"unknown schedule '{name}', have {sorted(SCHEDULES)}"
        ) from None


def names() -> tuple:
    """Registered schedule names, in registration order."""
    return tuple(SCHEDULES)


register(Schedule(
    "psum", psum_allreduce, costmodel.t_allreduce_best,
    rounds_fn=psum_rounds,
    doc="XLA-native all-reduce; priced as min(butterfly, ring) — what a "
        "tuned library achieves."))
register(Schedule(
    "tree", tree_allreduce, costmodel.t_tree_allreduce, pow2_only=True,
    rounds_fn=tree_rounds,
    doc="reduce-to-root + broadcast, 2·⌈log2 P⌉ rounds (paper §5.1)."))
register(Schedule(
    "butterfly", butterfly_allreduce, costmodel.t_butterfly_allreduce,
    pow2_only=True, rounds_fn=butterfly_rounds,
    doc="recursive doubling, ⌈log2 P⌉ rounds — latency-optimal."))
register(Schedule(
    "ring", ring_allreduce, costmodel.t_ring_allreduce, flat_only=True,
    rounds_fn=ring_rounds,
    doc="reduce-scatter + all-gather, 2(P−1) steps of n/P bytes — "
        "bandwidth-optimal."))
register(Schedule(
    "round_robin", round_robin_allreduce, costmodel.t_round_robin_allreduce,
    rounds_fn=round_robin_rounds,
    doc="Original EASGD's serialized master↔worker exchange, Θ(P) — the "
        "paper's baseline."))
register(Schedule(
    "hierarchical", hierarchical_grouped_allreduce, t_hierarchical_allreduce,
    flat_only=True, pow2_only=True, rounds_fn=hierarchical_rounds,
    doc="two-level divide-and-conquer (paper §6.2): ring within groups of "
        "2^⌈log2(P)/2⌉ ranks (ICI), butterfly across groups (DCI)."))


# ---------------------------------------------------------------------------
# derived helpers
# ---------------------------------------------------------------------------

def choose(n_bytes: float, p: int,
           net: costmodel.Network = costmodel.TPU_ICI,
           topology: costmodel.Topology | None = None,
           profile: costmodel.LinkProfile | None = None) -> str:
    """α–β-model-driven schedule choice (paper Table 2 reasoning):
    latency-bound small buffers → butterfly; bandwidth-bound → ring.
    butterfly is pow2-only, so a non-power-of-two group always gets ring
    (valid for any p) — the chooser never proposes an unrunnable schedule.

    With a ``topology`` (or a measured ``profile``, which carries one) the
    candidates are priced link-by-link via ``cost_topo``: ``hierarchical``
    joins the candidate set whenever its rounds resolve on that topology,
    and wins exactly when confining full-size traffic to ⌈log2 hosts⌉
    cross-host rounds beats the flat schedules' cross-host α bill — the
    paper's §6.2 regime. Candidate order (butterfly, ring, hierarchical)
    breaks ties, so hierarchical is only picked on a STRICT improvement
    and a uniform topology reproduces today's flat choice bitwise."""
    if profile is not None and topology is None:
        topology = profile.topology
    if p <= 1:
        return "psum"
    if topology is not None and not topology.uniform:
        cands = ["butterfly"] if p & (p - 1) == 0 else []
        cands.append("ring")
        try:
            get("hierarchical").rounds(p, n_bytes, topology.intra,
                                       topology=topology)
        except ValueError:
            pass
        else:
            cands.append("hierarchical")
        return min(cands,
                   key=lambda nm: get(nm).cost_topo(n_bytes, p, topology))
    if topology is not None:
        net = topology.intra
    if p & (p - 1) == 0 and get("butterfly").cost(n_bytes, p, net) <= \
            get("ring").cost(n_bytes, p, net):
        return "butterfly"
    return "ring"


def hierarchical_allreduce(x, inner_axis, outer_axis, inner="psum",
                           outer="psum"):
    """Two-level reduction: fast domain first, slow domain second.

    This is the paper's §6.2 divide-and-conquer generalized: reduce within
    the pod over ICI (cheap), then across pods over DCI (expensive) — the
    cross-pod message count is 1/pod_size of a flat all-reduce.
    """
    x = get(inner).allreduce(x, inner_axis)
    x = get(outer).allreduce(x, outer_axis)
    return x


def shard_map_allreduce(mesh, x, axis_name: str, algorithm: str = "auto"):
    """Run a registered schedule over a 1-D buffer replicated on
    ``axis_name`` and sharded on no other axis. Test/benchmark entry point."""
    if algorithm == "auto":
        algorithm = choose(x.size * x.dtype.itemsize, mesh.shape[axis_name])
    sched = get(algorithm)
    spec = P(axis_name)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec,),
        out_specs=spec,
        check_vma=False,
    )
    def run(xs):
        # xs: (1, n) slice per device along axis_name
        return sched.allreduce(xs[0], axis_name)[None]

    stacked = jnp.broadcast_to(x, (mesh.shape[axis_name],) + x.shape)
    return run(stacked)
