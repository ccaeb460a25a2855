"""Exchange-schedule round structure — the wire pattern as DATA, jax-free.

Each schedule in the ``repro.comm`` registry can describe itself as a list
of ROUNDS; a round is a list of point-to-point messages that fly
concurrently. This is the bridge between four consumers:

 * the α–β cost of a round is α + max_frac·n·β, and summing rounds
   reproduces the closed-form ``Schedule.cost_fn`` exactly (pinned by
   tests),
 * the repro.ps shared-memory runtime EXECUTES the same rounds over its
   transport mailboxes (``ps.execute_rounds``),
 * the repro.net MASTER executes them on its local mailbox for the
   centralized sync plane, and
 * the repro.net WORKERS execute them over direct worker↔worker TCP links
   for the peer-to-peer sync plane (``net.peer``) — each worker owns one
   mailbox row and ``Message.span`` tells it which byte range of the row a
   SEGMENT frame moves.

This module is deliberately jax-free (stdlib + ``core.costmodel`` only):
TCP worker processes deserialize rounds from the master's WELCOME and
import nothing heavier than numpy. The registry (``comm.schedules``)
re-exports everything here, so jax-side consumers see one definition.
"""
from __future__ import annotations

import dataclasses

from repro.core import costmodel

MASTER = -1   # in a parameter-server wiring the master is an endpoint of
#               its own, distinct from the p workers (round_robin uses it;
#               peer-to-peer schedules do not)


@dataclasses.dataclass(frozen=True)
class Message:
    """One point-to-point transfer inside a round.

    ``src``/``dst`` are worker ranks (or ``MASTER``). ``frac`` is the
    fraction of the buffer moved (ring moves 1/p chunks). For chunked
    schedules, the buffer is viewed as ``chunks`` equal slices and the
    receiver applies ``op`` to slice ``chunk``; chunk=None means the whole
    buffer. ``op`` is "add" (accumulate into the receiver) or "set"
    (overwrite) — receivers always read the sender's PRE-round value.
    """

    src: int
    dst: int
    frac: float = 1.0
    chunk: int | None = None
    chunks: int = 1
    op: str = "add"

    def span(self, n_elements: int) -> tuple[int, int]:
        """Element offsets ``[start, stop)`` of the buffer segment this
        message moves in an ``n_elements`` buffer (``chunks`` must divide
        it — the runtime pads rows to a multiple of P for exactly this).
        This is the SEGMENT frame's address on the p2p wire and the
        executor's slice on the shared-memory mailbox: one definition of
        which bytes a message touches."""
        if self.chunk is None:
            return 0, n_elements
        assert n_elements % self.chunks == 0, (n_elements, self.chunks)
        seg = n_elements // self.chunks
        return self.chunk * seg, (self.chunk + 1) * seg

    def nbytes(self, n_bytes: float) -> float:
        """Payload bytes this message moves out of an n_bytes buffer."""
        return self.frac * n_bytes


def round_robin_rounds(p, n_bytes=0.0, net=None, topology=None):
    """2·p serialized master↔worker messages: gather (add into the master,
    rank order — the same summation order as ``np.mean`` over workers, which
    the DES↔real bitwise cross-check relies on), then broadcast."""
    gather = [[Message(i, MASTER, op="add")] for i in range(p)]
    bcast = [[Message(MASTER, i, op="set")] for i in range(p)]
    return gather + bcast


def tree_rounds(p, n_bytes=0.0, net=None, topology=None):
    rounds = []
    d = 1
    while d < p:
        rounds.append([Message(i + d, i, op="add")
                       for i in range(0, p, 2 * d)])
        d *= 2
    d = p // 2
    while d >= 1:
        rounds.append([Message(i, i + d, op="set")
                       for i in range(0, p, 2 * d)])
        d //= 2
    return rounds


def butterfly_rounds(p, n_bytes=0.0, net=None, topology=None):
    rounds = []
    d = 1
    while d < p:
        rounds.append([Message(i, i ^ d, op="add") for i in range(p)])
        d *= 2
    return rounds


def ring_rounds(p, n_bytes=0.0, net=None, topology=None):
    rounds = []
    for s in range(p - 1):      # reduce-scatter
        rounds.append([Message(r, (r + 1) % p, frac=1.0 / p,
                               chunk=(r - s) % p, chunks=p, op="add")
                       for r in range(p)])
    for s in range(p - 1):      # all-gather
        rounds.append([Message(r, (r + 1) % p, frac=1.0 / p,
                               chunk=(r + 1 - s) % p, chunks=p, op="set")
                       for r in range(p)])
    return rounds


def psum_rounds(p, n_bytes=0.0, net=None, topology=None):
    """psum is 'whatever a tuned library picks': butterfly when the α–β
    model says latency-bound (and p is a power of two), else ring. On a
    NON-uniform topology the closed forms lie (they price one link class),
    so the two candidates are priced round-by-round over the actual links."""
    net = net or costmodel.TPU_ICI
    if topology is not None and not topology.uniform:
        if p & (p - 1) == 0:
            btf = butterfly_rounds(p)
            if t_rounds(btf, n_bytes, topology=topology) \
                    <= t_rounds(ring_rounds(p), n_bytes, topology=topology):
                return btf
        return ring_rounds(p)
    if p & (p - 1) == 0 and costmodel.t_butterfly_allreduce(n_bytes, p, net) \
            <= costmodel.t_ring_allreduce(n_bytes, p, net):
        return butterfly_rounds(p)
    return ring_rounds(p)


def _inner_size(p: int) -> int:
    """Two-level split p = inner × outer for the hierarchical schedule:
    inner = 2^⌈log2(p)/2⌉ (the near-square decomposition, paper §6.2's
    ICI-pod × DCI split collapsed onto one axis)."""
    if p <= 1:
        return 1
    log2p = p.bit_length() - 1
    return 1 << ((log2p + 1) // 2)


def topology_group(p: int, topology=None) -> int:
    """Group size for the hierarchical schedule: the topology's slot count
    when it actually tiles p (groups = hosts, so the inner ring stays on
    intra-host links and only the outer butterfly crosses hosts); otherwise
    the flat near-square split — which keeps default rounds byte-identical
    to before topologies existed (tests pin this)."""
    if topology is not None and topology.hosts > 1 and topology.p == p:
        return topology.slots
    return _inner_size(p)


def hierarchical_rounds(p, n_bytes=0.0, net=None, topology=None, group=None):
    """Grouped ring × butterfly (paper §6.2): ring reduce-scatter +
    all-gather inside each group of ``m`` ranks, then a recursive-doubling
    butterfly across the ``p // m`` groups. ``m`` comes from the topology
    (slots-per-host) when one is given, so the ring rides intra-host links
    and only ⌈log2 hosts⌉ rounds cross hosts. Any ``m ≥ 1`` works — the
    ring has no power-of-two needs — but the GROUP COUNT must be a power
    of two for the butterfly, which is how non-pow2 p (e.g. 24 = 4 hosts
    × 6 slots) becomes schedulable."""
    m = int(group) if group is not None else topology_group(p, topology)
    if m < 1 or p % m != 0:
        raise ValueError(
            f"hierarchical group size {m} does not tile p={p}")
    groups = p // m
    if groups & (groups - 1) != 0:
        raise ValueError(
            f"hierarchical needs a power-of-two group count, got "
            f"{groups} groups of {m} for p={p}")
    rounds = []
    for s in range(m - 1):      # inner grouped-ring reduce-scatter
        rounds.append([Message(g * m + j, g * m + (j + 1) % m, frac=1.0 / m,
                               chunk=(j - s) % m, chunks=m, op="add")
                       for g in range(groups) for j in range(m)])
    for s in range(m - 1):      # inner grouped-ring all-gather
        rounds.append([Message(g * m + j, g * m + (j + 1) % m, frac=1.0 / m,
                               chunk=(j + 1 - s) % m, chunks=m, op="set")
                       for g in range(groups) for j in range(m)])
    d = 1                       # outer butterfly across groups: rank g*m+j
    while d < groups:           # partners with (g^d)*m+j — for pow2 m this
        rounds.append([Message(g * m + j, (g ^ d) * m + j, op="add")
                       for g in range(groups) for j in range(m)])
        d *= 2                  # is byte-identical to the old i ^ (d*m) form
    return rounds


# ---------------------------------------------------------------------------
# pricing rounds over a (possibly heterogeneous) fabric
# ---------------------------------------------------------------------------

def _link_net(m: Message, net, topology):
    return topology.link(m.src, m.dst) if topology is not None else net


def t_rounds(rounds, n_bytes: float, net=None, topology=None,
             wid: int | None = None) -> float:
    """α–β time of a round structure with PER-MESSAGE link pricing: each
    round costs the max over its messages of ``link.α + frac·n·link.β``,
    rounds serialize. With a ``topology`` each message rides its own link
    class; without one every message rides ``net``, which is what
    ``Schedule.cost_from_rounds`` prices (α + max_frac·n·β per round).
    ``wid`` restricts to messages touching that worker — its OWN
    pacing deadline on a heterogeneous mesh, where an intra-host pair
    finishes its segment early and waits on cross-host peers at the
    blocking recv rather than by sleeping."""
    net = net or costmodel.TPU_ICI
    total = 0.0
    for rnd in rounds:
        worst = None
        for m in rnd:
            if wid is not None and m.src != wid and m.dst != wid:
                continue
            link = _link_net(m, net, topology)
            t = link.alpha + m.frac * n_bytes * link.beta
            if worst is None or t > worst:
                worst = t
        if worst is not None:
            total += worst
    return total


def t_rounds_buckets(rounds, n_elements: int, boundaries, net=None,
                     topology=None, wid: int | None = None) -> list[float]:
    """Per-bucket wire time of the bucketed VIEW of ``rounds``, with the
    same per-message link pricing as ``t_rounds``: bucket b pays, for every
    round it appears in, the max over its clipped messages of
    ``link.α + clipped_bytes·link.β``. The f64 payload of a clipped span
    (a, b) is (b − a)·8 bytes — exactly what the SEGMENT frame moves."""
    net = net or costmodel.TPU_ICI
    out = []
    for plan in bucket_rounds(rounds, n_elements, boundaries):
        t = 0.0
        for rnd in plan:
            worst = None
            for m, (a, b) in rnd:
                if wid is not None and m.src != wid and m.dst != wid:
                    continue
                link = _link_net(m, net, topology)
                tm = link.alpha + (b - a) * 8 * link.beta
                if worst is None or tm > worst:
                    worst = tm
            if worst is not None:
                t += worst
        out.append(t)
    return out


# ---------------------------------------------------------------------------
# bucketed view — the SAME rounds, clipped at bucket boundaries
# ---------------------------------------------------------------------------
#
# Bucketing for comm/compute overlap must not change a single bit of the
# result, so it is defined as a VIEW of the monolithic schedule rather than
# a per-bucket re-run of the schedule builder: every message keeps its
# src/dst/op and its position in the round order, and bucket b simply clips
# the message's element span to [lo_b, hi_b). Because buckets partition the
# row into disjoint element ranges, each element still sees exactly the
# same operations from the same sources in the same order as in the
# monolithic exchange — which is why bucketed ring/tree chains are
# bit-identical to monolithic ones (pinned by tests). Re-chunking the
# schedule per bucket instead (e.g. ring with chunks=p over each bucket)
# would reassign which rank owns which element's reduction chain and change
# the addition order — NOT bitwise-safe. Don't do that.

# jax-free copy of core.packing.ELASTIC_UPDATE_BLOCK (that module imports
# jax at the top; this one must stay importable in jax-free workers) —
# pinned equal by tests/test_bucketing.py
ELASTIC_UPDATE_ALIGN = 8 * 128 * 128


def default_bucket_boundaries(sizes, n_elements: int,
                              bucket_bytes: int) -> list[int]:
    """The runtime's boundary policy for ``bucket_bytes`` f64 payload bytes
    per bucket: align cuts to the fused-update kernel's block only when the
    buckets themselves are at least one block (small test problems need
    small buckets; the kernel falls back below a block anyway)."""
    target = max(1, int(bucket_bytes) // 8)
    align = ELASTIC_UPDATE_ALIGN if target >= ELASTIC_UPDATE_ALIGN else None
    return bucket_boundaries(sizes, n_elements, target, align=align)


def bucket_boundaries(sizes, n_elements: int, target_elems: int,
                      align: int | None = None) -> list[int]:
    """Cut offsets ``[0, b1, ..., n_elements]`` grouping consecutive layers
    into buckets of ~``target_elems`` elements.

    ``sizes`` is the per-layer element count sequence (e.g.
    ``Packer`` leaf sizes, or ``grad_fn.layer_sizes``); a cut is emitted at
    the first layer edge where the open bucket has reached ``target_elems``.
    With ``align``, each cut is rounded UP to a multiple of ``align`` (the
    fused-update kernel wants block-aligned buckets); cuts that would
    collide or overrun are dropped. Empty/None ``sizes`` falls back to
    uniform ``target_elems`` slabs. Always returns at least ``[0, n]``."""
    assert n_elements > 0 and target_elems > 0
    edges: list[int] = []
    if sizes:
        off = 0
        for s in sizes:
            off += int(s)
            edges.append(off)
    else:
        edges = list(range(target_elems, n_elements, target_elems))
        edges.append(n_elements)
    cuts = [0]
    for e in edges:
        if e >= n_elements:
            break
        if e - cuts[-1] >= target_elems:
            c = e if align is None else -(-e // align) * align
            if cuts[-1] < c < n_elements:
                cuts.append(c)
    cuts.append(n_elements)
    # align-rounding can make a later layer edge land on/before a cut
    out = [cuts[0]]
    for c in cuts[1:]:
        if c > out[-1]:
            out.append(c)
    return out


def clip_span(m: Message, n_elements: int, lo: int, hi: int
              ) -> tuple[int, int] | None:
    """Intersection of ``m.span(n_elements)`` with bucket ``[lo, hi)`` —
    ``None`` when the message moves no bytes of this bucket."""
    a, b = m.span(n_elements)
    a, b = max(a, lo), min(b, hi)
    return (a, b) if a < b else None


def bucket_rounds(rounds, n_elements: int, boundaries) -> list:
    """Per-bucket execution plans: element ``boundaries`` ``[0, .., n]`` →
    one plan per bucket, each a list of rounds of ``(message, (start,
    stop))`` pairs with spans clipped to the bucket (messages that miss the
    bucket are dropped, empty rounds kept so round indices — and therefore
    p2p frame sequence numbers — stay aligned across buckets)."""
    assert boundaries[0] == 0 and boundaries[-1] == n_elements, boundaries
    plans = []
    for lo, hi in zip(boundaries[:-1], boundaries[1:]):
        plan = []
        for rnd in rounds:
            clipped = []
            for m in rnd:
                span = clip_span(m, n_elements, lo, hi)
                if span is not None:
                    clipped.append((m, span))
            plan.append(clipped)
        plans.append(plan)
    return plans


# ---------------------------------------------------------------------------
# derived structure — what the p2p data plane needs to wire itself up
# ---------------------------------------------------------------------------

def bytes_from_rounds(rounds, n_bytes: float) -> float:
    """TOTAL payload bytes all messages of ``rounds`` move for an n-byte
    buffer (cost_from_rounds prices the same structure in TIME: per round
    α + max_frac·n·β, messages concurrent; this sums them in BYTES, every
    message counted — what the p2p per-link byte counters must add up to)."""
    return sum(m.nbytes(n_bytes) for rnd in rounds for m in rnd)


def peer_pairs(rounds) -> list[tuple[int, int]]:
    """The worker↔worker links a round structure needs: unordered (i, j)
    pairs with i < j, first-use order, MASTER-endpoint messages excluded
    (those ride the existing master links)."""
    pairs: list[tuple[int, int]] = []
    seen = set()
    for rnd in rounds:
        for m in rnd:
            if m.src == MASTER or m.dst == MASTER:
                continue
            pair = (min(m.src, m.dst), max(m.src, m.dst))
            if pair not in seen:
                seen.add(pair)
                pairs.append(pair)
    return pairs


def remap_rounds(rounds, rank_to_wid) -> list:
    """Relabel a round structure built over DENSE ranks 0..P'−1 onto real
    worker ids (``ft.membership.dense_rank_map``): after a membership
    change the schedule builders still produce dense indices, but the
    surviving wids are a sparse subset — e.g. {0, 1, 3} after wid 2 dies.
    MASTER endpoints pass through unchanged. Chunk ownership, fractions and
    op order are untouched, so the remapped structure prices and executes
    exactly like the dense one."""
    import dataclasses

    def _m(i):
        return MASTER if i == MASTER else rank_to_wid[i]

    return [[dataclasses.replace(m, src=_m(m.src), dst=_m(m.dst))
             for m in rnd] for rnd in rounds]


def rounds_to_wire(rounds) -> list:
    """JSON-ready form of a round structure (the master ships this to the
    p2p workers in WELCOME — workers never import the jax-side registry)."""
    return [[[m.src, m.dst, m.frac, m.chunk, m.chunks, m.op] for m in rnd]
            for rnd in rounds]


def rounds_from_wire(obj) -> list:
    """Inverse of ``rounds_to_wire``."""
    return [[Message(src, dst, frac, chunk, chunks, op)
             for src, dst, frac, chunk, chunks, op in rnd]
            for rnd in obj]
