"""The repro.ps runtime: the paper's nine algorithms EXECUTED, not simulated.

Same optimizer math as the DES simulator (``core.easgd_flat`` — shared, not
copied), same exchange registry (``repro.comm`` — the sync family executes
the registered schedule's ``Schedule.rounds`` message pattern over the
transport mailboxes), but time is wall-clock and concurrency is real
threads/processes on shared memory.

Concurrency disciplines (paper §4–5):

 * ``original_easgd`` — round-robin TURNSTILE: the master serves workers
   strictly in rank order (Θ(P) serialized exchange, the paper's baseline).
 * ``async_*``        — FCFS: workers hit the master lock in arrival order;
   with ``deterministic=True`` the turnstile replaces the lock, which is
   exactly the zero-jitter event order of the DES — the bitwise DES↔real
   cross-check runs in this mode.
 * ``hogwild_*``      — the SAME absorb with NO lock. Lock-free for real:
   concurrent in-place numpy updates tear and interleave.
 * ``sync_*``         — barriered rounds; the weight (EASGD) or gradient
   (SGD) all-reduce runs the registered schedule's message rounds in a comm
   executor thread. Sync EASGD posts start-of-step weights BEFORE computing
   gradients, so the exchange genuinely overlaps compute (paper §6.1.3);
   sync SGD needs the gradients first, so it cannot (§5.1).

τ (``EASGDConfig.tau``, the communication period) is honored by every
loop: workers take τ−1 local-only steps (``easgd_flat.local_step``)
between exchanges, so communication drops by 1/τ — Table 3's bandwidth
lever, executed. The DES cross-check and the bitwise tests run at τ=1
(the DES models τ=1 event orders).

``transport="tcp"`` dispatches the whole run to the repro.net master
server (workers are processes on other ends of a real wire — localhost
subprocesses by default, other hosts via launch/cluster); the PSResult
comes back in the same shape.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Optional

import numpy as np

from repro.comm import rounds as comm_rounds
from repro.comm import schedules as comm_schedules
from repro.core import costmodel, easgd_flat
from repro.core.async_engine import ALGORITHMS, PSEngine, SimConfig
from repro.core.easgd import EASGDConfig
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.ps.transport import PSContext, get_transport

SYNC = easgd_flat.SYNC_FAMILY

# default α–β network (only prices psum's butterfly-vs-ring choice for the
# sync rounds; the measured run doesn't consult it)
_DEFAULT_NET = costmodel.Network("PCIe3x16", 5e-6, 1 / 12e9)


@dataclasses.dataclass(frozen=True)
class PSConfig:
    algorithm: str
    n_workers: int = 4
    transport: str = "thread"        # "thread" | "process" | "tcp"
    schedule: str = "ring"           # sync-family exchange ("auto" allowed)
    total_iters: int = 1000
    deterministic: bool = False      # cyclic admission == DES zero-jitter
    eval_every_iters: int = 200
    net: costmodel.Network = _DEFAULT_NET
    # netem-style wire emulation: every master message / exchange round
    # ADDITIONALLY sleeps its α+nβ under this network (None: shared memory
    # IS the wire). The bytes still move and the concurrency discipline
    # still decides what serializes, overlaps, or amortizes — the sleep
    # restores the interconnect-bound regime the paper ran in (10GbE/IB),
    # which a single box's memcpy cannot reproduce. Charge the SAME network
    # to the DES (Calibration.sim_config(net=...)) for a fair cross-check.
    emulate_net: Optional[costmodel.Network] = None
    seed: int = 0
    # -- tcp transport only (repro.net) ------------------------------------
    wire_compression: str = "none"   # "none" | "sign_ef": per-link payload
    #                                  codec with error-feedback state (the
    #                                  framed 1-bit wire — core.compression)
    sync_plane: str = "master"       # "master": the net master executes the
    #                                  sync-family rounds on its local
    #                                  mailbox (every round funnels Θ(P·N)
    #                                  through its links); "p2p": workers
    #                                  execute the SAME rounds over direct
    #                                  worker↔worker links (net.peer) and
    #                                  the master degrades to control plane
    #                                  — Θ(N_center) on the master link
    tcp_host: str = "127.0.0.1"
    tcp_port: int = 0                # 0: ephemeral (launch/cluster pins one
    #                                  for multi-host rendezvous)
    spawn_workers: bool = True       # False: external workers join (--hosts)
    hb_interval_s: float = 2.0       # worker heartbeat period
    hb_timeout_s: float = 60.0       # master declares a silent link dead
    # -- bucketed overlap (sync family) -------------------------------------
    bucket_bytes: int = 0            # >0: partition the exchange row into
    #                                  per-layer-group buckets of ~this many
    #                                  payload bytes (comm.rounds.
    #                                  bucket_boundaries over the problem's
    #                                  layer_sizes) — a bitwise-identical
    #                                  VIEW of the same rounds. 0: monolithic
    overlap: bool = True             # p2p only: stream buckets while the
    #                                  gradient / per-bucket update computes
    #                                  (§6.1.3). False = the paper's
    #                                  no-overlap baseline — same math, the
    #                                  worker just waits out the wire
    # -- observability (repro.obs) ------------------------------------------
    trace: bool = False              # record per-thread spans (compute /
    #                                  waits / exchange rounds / buckets /
    #                                  updates) and return the merged,
    #                                  clock-aligned timeline + Table-3
    #                                  breakdown on PSResult.trace. Off by
    #                                  default: the hot paths then take no
    #                                  timestamps at all
    trace_dir: Optional[str] = None  # spill per-worker trace buffers as
    #                                  JSON files here instead of carrying
    #                                  them inline in BYE (process workers
    #                                  always spill; a temp dir is made if
    #                                  unset). Assumes a filesystem the
    #                                  master can read (localhost / NFS)
    # -- live telemetry plane (obs.live) ------------------------------------
    telemetry: bool = False          # stream heartbeat telemetry + master
    #                                  gauges into a ring-buffer time-series
    #                                  store, run the online straggler /
    #                                  health detector, serve STATS frames
    #                                  (tcp) and attach PSResult.health.
    #                                  Off by default: no store, no sampler
    #                                  thread, no acceptor — zero work
    telemetry_jsonl: Optional[str] = None    # stream one JSON line per
    #                                  sample to this path (implies
    #                                  telemetry; offline analysis /
    #                                  launch.monitor --from-jsonl)
    telemetry_interval_s: float = 0.0        # sampler/detector period;
    #                                  0 = follow hb_interval_s (one
    #                                  detector pass per heartbeat)
    straggler_factor: float = 2.0    # health detector deadline: flag a
    #                                  worker whose per-iteration delay
    #                                  exceeds this × the median
    #                                  (ft.straggler.BoundedStaleness)
    link_slow: Optional[tuple] = None        # per-wid emulated-wire
    #                                  multipliers (len n_workers, ≥1.0):
    #                                  worker i's master-link / p2p pacing
    #                                  deadlines stretch by link_slow[i] —
    #                                  a controlled straggler for testing
    #                                  detection (tcp + emulate_net only;
    #                                  clock-plane only, the math is
    #                                  untouched)
    # -- elastic membership (ft.membership) ---------------------------------
    elastic: bool = False            # tcp only: a worker death/preemption
    #                                  becomes a membership transition + a
    #                                  RECONFIGURE epoch instead of a dead
    #                                  run; rejoining workers are admitted
    #                                  mid-run. Off (default): failures
    #                                  raise exactly as before, and the
    #                                  happy path runs zero extra frames
    chaos: Optional[dict] = None     # deterministic fault injection
    #                                  (ft.chaos.ChaosSpec fields as a
    #                                  dict: wid / kill_at_iter / signal
    #                                  "kill"|"term" / dial_refuse_s) —
    #                                  serialized to the spawned workers'
    #                                  REPRO_CHAOS env; tcp only
    # -- heterogeneous fabric (topology-aware scale-out) --------------------
    topology: Optional[costmodel.Topology] = None    # hosts × slots link
    #                                  model: it REPLACES emulate_net for
    #                                  the sync family — every pacing sleep
    #                                  (master rounds, p2p segment
    #                                  deadlines) prices each message over
    #                                  ITS link class (fast intra-host /
    #                                  slow cross-host), and schedule="auto"
    #                                  ranks candidates per-topology
    link_profile: Optional[costmodel.LinkProfile] = None     # a MEASURED
    #                                  per-link-class profile (ps.
    #                                  measured_link_profile / calibrate):
    #                                  when set, "auto" choice prices over
    #                                  it instead of the nominal topology

    def __post_init__(self):
        assert self.algorithm in ALGORITHMS, self.algorithm
        assert self.bucket_bytes >= 0, self.bucket_bytes
        assert self.wire_compression in ("none", "sign_ef"), \
            self.wire_compression
        # the shared-memory transports have no wire to compress — a config
        # that claims compression there would silently report raw bytes
        assert self.wire_compression == "none" or self.transport == "tcp", (
            f"wire_compression='{self.wire_compression}' is a tcp-transport "
            f"feature (transport='{self.transport}' moves no frames)")
        assert self.sync_plane in ("master", "p2p"), self.sync_plane
        # the p2p data plane is worker↔worker sockets executing the sync
        # family's rounds — it has no meaning off tcp or off that family
        assert self.sync_plane == "master" or (
            self.transport == "tcp" and self.algorithm in SYNC), (
            f"sync_plane='p2p' needs transport='tcp' and a sync-family "
            f"algorithm (got transport='{self.transport}', "
            f"algorithm='{self.algorithm}') — only the sync family "
            f"executes Schedule.rounds, and only repro.net has peer links")
        assert self.telemetry_interval_s >= 0.0, self.telemetry_interval_s
        assert self.straggler_factor > 1.0, self.straggler_factor
        if self.link_slow is not None:
            assert self.transport == "tcp", (
                "link_slow stretches per-link wire pacing — only the tcp "
                f"transport has per-worker links (transport="
                f"'{self.transport}')")
            assert self.emulate_net is not None, (
                "link_slow multiplies EMULATED wire time; without "
                "emulate_net there is no pacing to stretch")
            assert len(self.link_slow) == self.n_workers, (
                f"link_slow needs one factor per worker "
                f"({len(self.link_slow)} != {self.n_workers})")
            assert all(f >= 1.0 for f in self.link_slow), self.link_slow
        assert not self.elastic or self.transport == "tcp", (
            "elastic membership reconfigures real links — only the tcp "
            f"transport has them (transport='{self.transport}')")
        if self.chaos is not None:
            assert self.transport == "tcp", (
                "chaos injection targets spawned tcp worker processes "
                f"(transport='{self.transport}')")
            from repro.ft.chaos import ChaosSpec
            ChaosSpec.from_config(self.chaos)   # validates the fields
        if self.topology is not None:
            assert self.algorithm in SYNC, (
                "a topology prices the sync family's exchange rounds — "
                f"algorithm '{self.algorithm}' has none")
            assert self.topology.p == self.n_workers, (
                f"topology is {self.topology.hosts}x{self.topology.slots}="
                f"{self.topology.p} slots but n_workers={self.n_workers}")
            assert self.transport in ("thread", "tcp"), (
                f"topology pacing exists on the thread and tcp planes "
                f"(transport='{self.transport}')")
            assert self.emulate_net is None, (
                "topology REPLACES emulate_net: per-link pacing and the "
                "global emulated wire would double-charge the clock")
            assert not self.elastic, (
                "topology-aware pacing + elastic membership are not yet "
                "composed (an epoch's survivors no longer tile the "
                "declared hosts x slots grid)")
        if self.link_profile is not None:
            assert self.topology is not None, (
                "link_profile rides a topology — set PSConfig.topology to "
                "the fabric the profile was measured on")

    @property
    def telemetry_on(self) -> bool:
        return self.telemetry or self.telemetry_jsonl is not None

    def telemetry_period_s(self) -> float:
        return self.telemetry_interval_s or self.hb_interval_s

    def link_slow_factor(self, wid: int) -> float:
        if self.link_slow is None:
            return 1.0
        return float(self.link_slow[wid])

    def resolved_schedule(self, n_bytes: float,
                          profile: Optional[costmodel.LinkProfile] = None
                          ) -> str:
        """Schedule name for an n-byte exchange. "auto" ranks candidates
        over, in preference order: an explicitly passed measured
        ``profile``, ``self.link_profile``, the nominal ``self.topology``,
        else the flat ``self.net`` — exactly today's choice when no
        topology is in play."""
        if self.schedule != "auto":
            return comm_schedules.get(self.schedule).name
        prof = profile if profile is not None else self.link_profile
        if prof is not None:
            return comm_schedules.choose(n_bytes, self.n_workers,
                                         profile=prof)
        if self.topology is not None:
            return comm_schedules.choose(n_bytes, self.n_workers,
                                         topology=self.topology)
        return comm_schedules.choose(n_bytes, self.n_workers, self.net)

    def hb_interval_eff_s(self, p: Optional[int] = None) -> float:
        """Heartbeat period scaled with mesh size: P links at a fixed 2 s
        period flood the master's reader threads and trip false hb_stale
        verdicts at high P. Scale by max(1, P/16) — every P ≤ 16 config
        keeps EXACTLY its configured period (tests pin this), P = 64 beats
        4× slower."""
        pp = self.n_workers if p is None else p
        return self.hb_interval_s * max(1.0, pp / 16.0)

    def hb_timeout_eff_s(self, p: Optional[int] = None) -> float:
        """Staleness threshold that scales WITH the interval: never below
        the configured timeout, and at least 12 effective periods so a
        scaled-up interval cannot outrun its own deadline."""
        return max(self.hb_timeout_s, 12.0 * self.hb_interval_eff_s(p))

    def t_msg_emulated(self, n_bytes: float) -> float:
        """Per-message emulated wire time (0 without emulation)."""
        if self.emulate_net is None:
            return 0.0
        return costmodel.t_msg(n_bytes, self.emulate_net)


@dataclasses.dataclass
class PSResult:
    algorithm: str
    transport: str
    schedule: str
    history: list                    # [(wall_s, total_iters, metric)]
    total_time_s: float
    total_iters: int
    counters: dict                   # sync_rounds / messages / wire_bytes
    final_metric: float
    center: np.ndarray
    workers: np.ndarray              # (P, n) final worker weights
    trace: Optional[dict] = None     # cfg.trace: the merged, clock-aligned
    #                                  timeline (obs.report.merge_traces
    #                                  shape) with a "report" breakdown
    health: Optional[dict] = None    # cfg.telemetry: the live plane's
    #                                  summary — structured health events
    #                                  (straggler / hb_stale / recovered /
    #                                  worker_left), currently-flagged
    #                                  workers, final per-worker telemetry
    #                                  (obs.live.LiveMonitor.health())


# ---------------------------------------------------------------------------
# the sync-family exchange: execute the registry's message rounds
# ---------------------------------------------------------------------------

def _sleep_until(deadline: float) -> None:
    """Absolute-deadline sleep (``time.monotonic`` clock): oversleeps on a
    loaded box don't accumulate — the next deadline is computed from the
    schedule, not from when this sleep happened to return."""
    dt = deadline - time.monotonic()
    if dt > 0:
        time.sleep(dt)


def _apply_round(mailbox, n: int, rnd, counters=None) -> None:
    """One message round: receivers read the senders' PRE-round values
    (snapshot, then apply) — messages within a round are concurrent.
    ``Message.span`` addresses the slice each message moves — the same
    offsets the p2p data plane puts on the wire as SEGMENT frames."""
    row_len = mailbox.shape[-1]
    payloads = []
    for m in rnd:
        a, b = m.span(row_len)
        payloads.append((m, mailbox[m.src, a:b].copy()))
    for m, pay in payloads:
        a, b = m.span(row_len)
        tgt = mailbox[m.dst, a:b]
        if m.op == "add":
            tgt += pay
        else:
            tgt[:] = pay
    if counters is not None:
        obs_metrics.count_round(counters, rnd, n)


def _apply_clipped_round(mailbox, rnd_clipped) -> None:
    """``_apply_round`` over pre-clipped ``(message, (a, b))`` pairs — the
    bucketed view's unit of work. Same snapshot-then-apply discipline."""
    payloads = []
    for m, (a, b) in rnd_clipped:
        payloads.append((m, a, b, mailbox[m.src, a:b].copy()))
    for m, a, b, pay in payloads:
        tgt = mailbox[m.dst, a:b]
        if m.op == "add":
            tgt += pay
        else:
            tgt[:] = pay


def execute_rounds(mailbox, n: int, rounds, counters=None,
                   boundaries=None, tracer=None) -> None:
    """Apply one allreduce = the schedule's message rounds over the mailbox
    (rows 0..P-1 = workers, row P = the master endpoint used by
    round_robin). Rounds are serialized — the execution IS the α–β model's
    structure.

    ``boundaries`` (bucket cuts over the row) switches to the bucketed
    VIEW: the same rounds execute bucket-major with every message span
    clipped per bucket (``comm.rounds.bucket_rounds``). Buckets partition
    the row into disjoint element ranges, so each element sees the same
    ops from the same sources in the same order — bitwise-identical to the
    monolithic path (pinned by tests). Counters stay schedule-level: one
    exchange contributes the SAME sync_rounds/messages/wire_bytes either
    way (bucketing repartitions frames, not the schedule's cost).
    """
    mailbox[-1].fill(0.0)            # master endpoint accumulates from zero
    if boundaries is not None and len(boundaries) > 2:
        row_len = mailbox.shape[-1]
        plans = comm_rounds.bucket_rounds(rounds, row_len, boundaries)
        for bidx, plan in enumerate(plans):
            t0 = time.perf_counter() if tracer is not None else 0.0
            for rnd_clipped in plan:
                _apply_clipped_round(mailbox, rnd_clipped)
            if tracer is not None:
                tracer.record(obs_trace.BUCKET, t0, time.perf_counter(),
                              bidx)
        if counters is not None:
            for rnd in rounds:
                obs_metrics.count_round(counters, rnd, n)
        return
    for i, rnd in enumerate(rounds):
        t0 = time.perf_counter() if tracer is not None else 0.0
        _apply_round(mailbox, n, rnd, counters)
        if tracer is not None:
            tracer.record(obs_trace.ROUND, t0, time.perf_counter(), i)


def _comm_executor(ctx: PSContext) -> None:
    """The sync family's 'NIC': runs the allreduce rounds between barriers
    A and B of every training round while the workers compute (Sync EASGD —
    real overlap) or wait (Sync SGD). sync_easgd's version-flipped center
    needs no post-update barrier (see ``_sync_worker``), so its round has
    two barriers; sync_sgd keeps a third."""
    v = ctx.views()
    counters = {"sync_rounds": ctx.sync_rounds, "messages": ctx.messages,
                "wire_bytes": ctx.wire_bytes}
    tau = max(ctx.easgd.tau, 1)
    n_rounds = -(-ctx.cfg.total_iters // (ctx.cfg.n_workers * tau))
    third = ctx.cfg.algorithm == "sync_sgd"
    tr = obs_trace.tracer("comm") if ctx.cfg.trace else None
    _pc = time.perf_counter
    # emulated wire: the message rounds serialize, so one exchange costs
    # Σ (α + max_frac·n·β) on top of the real copies — paced as a single
    # absolute deadline per exchange to be robust to oversleep. With a
    # topology each round is priced over its own link classes instead of
    # one global wire (comm.rounds.t_rounds)
    if ctx.cfg.topology is not None:
        t_wire = comm_rounds.t_rounds(ctx.rounds, ctx.n * 8,
                                      topology=ctx.cfg.topology)
    else:
        t_wire = sum(
            ctx.cfg.t_msg_emulated(max(m.frac for m in rnd) * ctx.n * 8)
            for rnd in ctx.rounds)
    try:
        for _ in range(n_rounds):
            if tr is not None:
                t0 = _pc()
            ctx.barrier.wait()       # A: mailboxes posted
            if tr is not None:
                tr.record(obs_trace.BARRIER, t0, (tx := _pc()), 0)
            deadline = time.monotonic() + t_wire
            execute_rounds(v.mailbox, ctx.n, ctx.rounds, counters,
                           boundaries=getattr(ctx, "boundaries", None),
                           tracer=tr)
            if t_wire:
                _sleep_until(deadline)
            if tr is not None:
                tr.record(obs_trace.EXCHANGE, tx, (t0 := _pc()))
            ctx.barrier.wait()       # B: exchange complete
            if tr is not None:
                tr.record(obs_trace.BARRIER, t0, _pc(), 1)
            if third:
                ctx.barrier.wait()   # C: master update complete
    except threading.BrokenBarrierError:
        pass
    except Exception:                # noqa: BLE001 — surface via err flag
        ctx.err.value = 1
        ctx.barrier.abort()


# ---------------------------------------------------------------------------
# worker loops
# ---------------------------------------------------------------------------

def worker_main(ctx: PSContext, wid: int) -> None:
    w0, grad_fn, _ = ctx.built_problem()
    # warm caches/pages before the start gate so the measured clock sees
    # steady state; ids ≤ −2 are private RNG streams (worker streams and
    # therefore the DES↔real iterate equality are untouched)
    wu = np.asarray(w0, np.float64).copy()
    for k in range(2):
        grad_fn(wu, k, -(wid + 2))
    ctx.start_barrier.wait()
    tr = obs_trace.tracer("main", wid=wid) if ctx.cfg.trace else None
    algo = ctx.cfg.algorithm
    if algo in SYNC:
        _sync_worker(ctx, wid, grad_fn, tr)
    elif algo == "original_easgd" or ctx.cfg.deterministic:
        _turnstile_worker(ctx, wid, grad_fn, tr)
    elif algo.startswith("hogwild"):
        _hogwild_worker(ctx, wid, grad_fn, tr)
    else:
        _fcfs_worker(ctx, wid, grad_fn, tr)
    if tr is not None and ctx.cfg.trace_dir:
        # process transport: the registry dies with this process — spill
        # the buffer to disk for the launcher to merge (perf_counter is
        # system-wide CLOCK_MONOTONIC, so offsets between local processes
        # are already ~0 and no clock sync is needed)
        obs_trace.dump_spill(ctx.cfg.trace_dir, wid, {
            "clock": {"offset_s": 0.0, "rtt_s": 0.0},
            "threads": {"main": tr.spans()},
            "dropped": tr.dropped,
        })


def _turnstile_worker(ctx, wid, grad_fn, tr=None):
    """Strict cyclic admission: worker ``turn % P`` owns the master next.
    This is Original EASGD's round-robin wire — and, for the async family
    under ``deterministic=True``, exactly the DES zero-jitter event order.

    original_easgd computes its gradient INSIDE the turn: the master serves
    one worker at a time end to end, so the whole pipeline serializes —
    the Θ(P) behavior the paper attacks (and what the DES charges). The
    async family computes ahead of the turn (w⁽ⁱ⁾ only changes during our
    own turn and the gradient never reads W̄, so the iterates are identical
    either way — only the clock differs)."""
    v, e = ctx.views(), ctx.easgd
    algo, P, total = ctx.cfg.algorithm, ctx.cfg.n_workers, ctx.cfg.total_iters
    w, vel = v.workers_w[wid], v.workers_v[wid]
    serial_compute = algo == "original_easgd"
    t_msg = ctx.cfg.t_msg_emulated(ctx.n * 8)
    tau = max(e.tau, 1)
    total_turns = -(-total // tau)           # one turn = one exchange = τ steps
    local_step = 0
    _pc = time.perf_counter

    def _tau_block():
        """τ−1 local-only steps + the exchange gradient."""
        nonlocal local_step
        if tr is not None:
            t0 = _pc()
        for _ in range(tau - 1):
            g = grad_fn(w, local_step, wid)
            easgd_flat.local_step(algo, w, vel, g, e)
            local_step += 1
        if tr is not None and tau > 1:
            tr.record(obs_trace.LOCAL_STEP, t0, (t0 := _pc()), tau - 1)
        g = grad_fn(w, local_step, wid)
        local_step += 1
        if tr is not None:
            tr.record(obs_trace.COMPUTE, t0, _pc())
        return g

    while True:
        grad = None if serial_compute else _tau_block()
        if tr is not None:
            t0 = _pc()
        with ctx.turn_cond:
            while ctx.turn.value < total_turns and ctx.turn.value % P != wid:
                ctx.turn_cond.wait(0.05)
            if tr is not None:
                tr.record(obs_trace.TURN_WAIT, t0, (t0 := _pc()))
            if ctx.turn.value >= total_turns:
                ctx.turn_cond.notify_all()
                return
            if t_msg:                        # master → worker (W̄ down)
                _sleep_until(time.monotonic() + t_msg)
                if tr is not None:
                    tr.record(obs_trace.COMM_WAIT, t0, (t0 := _pc()), 0)
            if serial_compute:
                grad = _tau_block()
                if tr is not None:
                    t0 = _pc()
                easgd_flat.master_absorb_round_robin(
                    v.center, w, vel, grad, e)
            else:
                easgd_flat.master_absorb(
                    algo, v.center, v.master_vel, w, vel, grad, e)
            if tr is not None:
                tr.record(obs_trace.UPDATE, t0, (t0 := _pc()))
            if t_msg:                        # worker → master (W⁽ⁱ⁾ up)
                _sleep_until(time.monotonic() + t_msg)
                if tr is not None:
                    tr.record(obs_trace.COMM_WAIT, t0, _pc(), 1)
            ctx.turn.value += 1
            ctx.iters.value += tau
            ctx.messages.value += 2          # worker↔master, both ways
            ctx.wire_bytes.value += 2 * ctx.n * 8
            ctx.turn_cond.notify_all()


def _fcfs_worker(ctx, wid, grad_fn, tr=None):
    """Async family: first-come-first-served on the master lock."""
    v, e = ctx.views(), ctx.easgd
    algo, total = ctx.cfg.algorithm, ctx.cfg.total_iters
    w, vel = v.workers_w[wid], v.workers_v[wid]
    t_msg = ctx.cfg.t_msg_emulated(ctx.n * 8)
    tau = max(e.tau, 1)
    local_step = 0
    _pc = time.perf_counter
    while ctx.iters.value < total:
        if tr is not None:
            t0 = _pc()
        for _ in range(tau - 1):             # τ−1 local-only steps
            g = grad_fn(w, local_step, wid)
            easgd_flat.local_step(algo, w, vel, g, e)
            local_step += 1
        if tr is not None and tau > 1:
            tr.record(obs_trace.LOCAL_STEP, t0, (t0 := _pc()), tau - 1)
        grad = grad_fn(w, local_step, wid)
        local_step += 1
        if tr is not None:
            tr.record(obs_trace.COMPUTE, t0, (t0 := _pc()))
        deadline = None
        with ctx.master_lock:
            if tr is not None:
                tr.record(obs_trace.TURN_WAIT, t0, (t0 := _pc()))
            if ctx.iters.value >= total:
                return
            if t_msg:
                # the ONE master link serializes both messages of every
                # exchange: reserve wire time as an absolute deadline (the
                # sleep happens OUTSIDE the lock — the wire is busy, the
                # master CPU is not)
                start = max(time.monotonic(), ctx.wire_free_at.value)
                deadline = start + 2 * t_msg
                ctx.wire_free_at.value = deadline
            easgd_flat.master_absorb(
                algo, v.center, v.master_vel, w, vel, grad, e)
            ctx.iters.value += tau
            ctx.messages.value += 2
            ctx.wire_bytes.value += 2 * ctx.n * 8
            if tr is not None:
                tr.record(obs_trace.UPDATE, t0, (t0 := _pc()))
        if deadline is not None:
            _sleep_until(deadline)
            if tr is not None:
                tr.record(obs_trace.COMM_WAIT, t0, _pc())


def _hogwild_worker(ctx, wid, grad_fn, tr=None):
    """The SAME absorb as FCFS with NO lock — concurrent in-place updates
    of the shared center interleave (and tear) for real. Termination is by
    per-worker quota: the racy shared counter is monitoring-only."""
    v, e = ctx.views(), ctx.easgd
    algo, P, total = ctx.cfg.algorithm, ctx.cfg.n_workers, ctx.cfg.total_iters
    w, vel = v.workers_w[wid], v.workers_v[wid]
    t_msg = ctx.cfg.t_msg_emulated(ctx.n * 8)
    tau = max(e.tau, 1)
    quota = total // P + (1 if wid < total % P else 0)
    _pc = time.perf_counter
    for local_step in range(quota):
        if tr is not None:
            t0 = _pc()
        grad = grad_fn(w, local_step, wid)
        if (local_step + 1) % tau and local_step != quota - 1:
            easgd_flat.local_step(algo, w, vel, grad, e)   # τ local-only
            if tr is not None:
                tr.record(obs_trace.LOCAL_STEP, t0, _pc(), 1)
            ctx.iters.value += 1             # racy — monitoring only
            continue
        if tr is not None:
            tr.record(obs_trace.COMPUTE, t0, (t0 := _pc()))
        deadline = (time.monotonic() + 2 * t_msg) if t_msg else None
        easgd_flat.master_absorb(
            algo, v.center, v.master_vel, w, vel, grad, e)
        if tr is not None:
            tr.record(obs_trace.UPDATE, t0, (t0 := _pc()))
        if deadline is not None:
            _sleep_until(deadline)           # lock-free: wire times OVERLAP
            if tr is not None:
                tr.record(obs_trace.COMM_WAIT, t0, _pc())
        ctx.iters.value += 1                 # racy — monitoring only
        ctx.messages.value += 2
        ctx.wire_bytes.value += 2 * ctx.n * 8


def _sync_worker(ctx, wid, grad_fn, tr=None):
    """Barriered rounds; barriers are shared with the comm executor.

    sync_easgd: post W_t → [A] → grad ∥ allreduce → [B] → worker rule →
                rank 0 applies eq 2. TWO barriers per round: W̄ is
                version-flipped — round k reads W̄[k mod 2] while rank 0
                writes W̄[(k+1) mod 2], so the center update needs no
                post-update barrier (real readers and the writer never
                touch the same buffer; the next round's A orders the flip).
    sync_sgd:   grad → post → [A] → allreduce (workers idle — a gradient
                exchange cannot overlap its own compute, §5.1) → [B] →
                rank 0 momentum step on ḡ → [C] → all copy W̄.
    """
    v, e = ctx.views(), ctx.easgd
    algo, P, total = ctx.cfg.algorithm, ctx.cfg.n_workers, ctx.cfg.total_iters
    w, vel = v.workers_w[wid], v.workers_v[wid]
    n = ctx.n
    tau = max(e.tau, 1)
    n_rounds = -(-total // (P * tau))
    it = 0
    _pc = time.perf_counter

    def _local_block():
        """τ−1 local-only steps before the barriered exchange step."""
        nonlocal it
        if tr is not None and tau > 1:
            t0 = _pc()
        for _ in range(tau - 1):
            g = grad_fn(w, it, wid)
            easgd_flat.local_step(algo, w, vel, g, e)
            it += 1
        if tr is not None and tau > 1:
            tr.record(obs_trace.LOCAL_STEP, t0, _pc(), tau - 1)

    if algo == "sync_easgd":
        versions = (v.center, v.center_alt)
        for step in range(n_rounds):
            _local_block()
            c_read, c_write = versions[step % 2], versions[(step + 1) % 2]
            v.mailbox[wid, :n] = w           # start-of-exchange-step weights
            if tr is not None:
                t0 = _pc()
            ctx.barrier.wait()               # A — exchange begins
            if tr is not None:
                tr.record(obs_trace.BARRIER, t0, (t0 := _pc()), 0)
            grad = grad_fn(w, it, wid)       # …and overlaps this compute
            it += 1
            if tr is not None:
                tr.record(obs_trace.COMPUTE, t0, (t0 := _pc()))
            ctx.barrier.wait()               # B — sum of W_t in every row
            if tr is not None:
                tr.record(obs_trace.BARRIER, t0, (t0 := _pc()), 1)
            easgd_flat.worker_step(algo, w, vel, grad, c_read, e)
            if wid == 0:
                c_write[:] = c_read
                easgd_flat.sync_master_easgd(
                    c_write, v.mailbox[0, :n] / P, P, e)
                ctx.iters.value += P * tau
            if tr is not None:
                tr.record(obs_trace.UPDATE, t0, _pc())
        # NOTE: after an odd round count the final W̄ lives in center_alt;
        # the LAUNCHER copies it back post-join (doing it here would race
        # with the other workers' last worker_step, which reads .center)
        return
    for step in range(n_rounds):             # sync_sgd
        _local_block()
        if tr is not None:
            t0 = _pc()
        grad = grad_fn(w, it, wid)
        it += 1
        if tr is not None:
            tr.record(obs_trace.COMPUTE, t0, (t0 := _pc()))
        v.mailbox[wid, :n] = grad
        ctx.barrier.wait()                   # A — gradient allreduce
        ctx.barrier.wait()                   # B — workers idle through both
        if tr is not None:
            tr.record(obs_trace.BARRIER, t0, (t0 := _pc()), 1)
        if wid == 0:
            easgd_flat.sync_master_sgd(
                v.center, v.master_vel, v.mailbox[0, :n] / P, e)
            ctx.iters.value += P * tau
            if tr is not None:
                tr.record(obs_trace.UPDATE, t0, (t0 := _pc()))
        ctx.barrier.wait()                   # C — W̄ updated
        if tr is not None:
            tr.record(obs_trace.BARRIER, t0, _pc(), 2)
        w[:] = v.center


# ---------------------------------------------------------------------------
# the launcher
# ---------------------------------------------------------------------------

def run_ps(problem, easgd: EASGDConfig, cfg: PSConfig,
           eval_fn_override=None, join_timeout_s: float = 600.0) -> PSResult:
    """Run one algorithm for real. ``problem`` is a ``ProblemSpec`` or a
    prebuilt (w0, grad_fn, eval_fn) triple (thread transport only)."""
    tr = get_transport(cfg.transport)
    if hasattr(tr, "run"):
        # network transports own the whole run (no shared buffers to hand
        # out): repro.net's master server returns the same PSResult shape
        return tr.run(problem, easgd, cfg,
                      eval_fn_override=eval_fn_override,
                      join_timeout_s=join_timeout_s)
    built = problem.build() if hasattr(problem, "build") else problem
    w0, _, eval_fn = built
    if eval_fn_override is not None:
        eval_fn = eval_fn_override
    if cfg.trace:
        obs_trace.drain()                    # clean registry for THIS run
        if tr.name == "process" and not cfg.trace_dir:
            # worker tracers live in other processes: give them somewhere
            # to spill (BYE-equivalent; the launcher merges from disk)
            import tempfile
            cfg = dataclasses.replace(
                cfg, trace_dir=tempfile.mkdtemp(prefix="repro-trace-"))
    w0 = np.asarray(w0, np.float64)
    n, P = w0.size, cfg.n_workers
    sched_name = cfg.resolved_schedule(n * 8)
    rounds = (comm_schedules.get(sched_name)
              .rounds(P, n * 8, cfg.net, topology=cfg.topology)
              if cfg.algorithm in SYNC else [])
    padded = n + (-n) % max(P, 1)

    shapes = {"center": (n,), "center_alt": (n,), "master_vel": (n,),
              "workers_w": (P, n), "workers_v": (P, n),
              "mailbox": (P + 1, padded)}
    buffers = {k: tr.array(*shape) for k, shape in shapes.items()}
    prims = {
        "master_lock": tr.lock(),
        "barrier": tr.barrier(P + 1),            # workers + comm executor
        "start_barrier": tr.barrier(P + 1),      # workers + launcher
        "turn_cond": tr.condition(),
        "wire_free_at": tr.float_slot(),
        "turn": tr.int_slot(), "iters": tr.int_slot(),
        "sync_rounds": tr.int_slot(), "messages": tr.int_slot(),
        "wire_bytes": tr.int_slot(), "err": tr.int_slot(),
    }
    worker_problem = built if tr.name == "thread" else problem
    bounds = None
    if cfg.bucket_bytes > 0 and cfg.algorithm in SYNC:
        # layer edges come from the problem when it declares them (zoo
        # problems attach ``layer_sizes`` to their grad_fn); uniform slabs
        # otherwise — either way the exchange math is bitwise unchanged
        bounds = comm_rounds.default_bucket_boundaries(
            getattr(built[1], "layer_sizes", None), padded, cfg.bucket_bytes)
    ctx = PSContext(cfg, easgd, n, padded, buffers, shapes, worker_problem,
                    rounds, prims, boundaries=bounds)
    v = ctx.views()
    v.center[:] = w0
    v.center_alt[:] = w0
    v.workers_w[:] = w0[None]

    handles = tr.launch(ctx)
    comm_thread = None
    if cfg.algorithm in SYNC:
        comm_thread = threading.Thread(target=_comm_executor, args=(ctx,),
                                       daemon=True)
        comm_thread.start()

    # watchdog: a worker dying outside our try/except (e.g. a spawn-import
    # failure) must break the barriers instead of hanging the launcher
    stop_watch = threading.Event()

    def _watchdog():
        while not stop_watch.is_set():
            for h in handles:
                if getattr(h, "exitcode", None) not in (None, 0):
                    ctx.err.value = 1
                    for b in (ctx.barrier, ctx.start_barrier):
                        try:
                            b.abort()
                        except Exception:    # noqa: BLE001
                            pass
                    return
            time.sleep(0.05)

    watchdog = threading.Thread(target=_watchdog, daemon=True)
    watchdog.start()
    try:
        ctx.start_barrier.wait(join_timeout_s)   # workers built problems
    except threading.BrokenBarrierError:
        stop_watch.set()
        tr.join(handles, timeout=1.0)
        raise RuntimeError(
            f"ps workers failed to start (algorithm={cfg.algorithm}, "
            f"transport={cfg.transport})") from None
    t0 = time.perf_counter()
    history, last_eval = [], 0
    deadline = t0 + join_timeout_s
    # live telemetry (obs.live): the shared-memory transports have no
    # per-worker heartbeats, so the launcher poll loop samples AGGREGATE
    # gauges only (store wid −1) — per-worker series and straggler
    # detection need per-worker links, i.e. the tcp transport
    live = None
    if cfg.telemetry_on:
        from repro.obs import live as obs_live
        live = obs_live.LiveMonitor(
            P, deadline_factor=cfg.straggler_factor,
            hb_interval_s=cfg.hb_interval_s,
            jsonl_path=cfg.telemetry_jsonl,
            meta={"algorithm": cfg.algorithm, "transport": cfg.transport})
        live_period = cfg.telemetry_period_s()
        next_sample = time.monotonic() + live_period

    def _live_gauges():
        el = max(time.perf_counter() - t0, 1e-9)
        return {"iters": ctx.iters.value,
                "rate_ips": round(ctx.iters.value / el, 2),
                "wire_bytes": ctx.wire_bytes.value,
                "messages": ctx.messages.value,
                "sync_rounds": ctx.sync_rounds.value}

    while any(h.is_alive() for h in handles):
        if ctx.err.value:
            break
        it = ctx.iters.value
        if it - last_eval >= cfg.eval_every_iters:
            history.append((time.perf_counter() - t0, it,
                            float(eval_fn(v.center.copy()))))
            last_eval = it
        if live is not None and time.monotonic() >= next_sample:
            live.sample(gauges=_live_gauges())
            next_sample += live_period
        if time.perf_counter() > deadline:
            break
        time.sleep(1e-3)
    total_time = time.perf_counter() - t0
    stop_watch.set()
    ok = tr.join(handles, timeout=5.0)
    if comm_thread is not None:
        comm_thread.join(timeout=5.0)
    if ctx.err.value or not ok:
        raise RuntimeError(
            f"ps run failed (algorithm={cfg.algorithm}, "
            f"transport={cfg.transport}, err={ctx.err.value}, joined={ok})")

    n_sync_rounds = -(-cfg.total_iters // (P * max(easgd.tau, 1)))
    if cfg.algorithm == "sync_easgd" and n_sync_rounds % 2 == 1:
        v.center[:] = v.center_alt           # final version of the flip
    total_iters = (cfg.total_iters if cfg.algorithm.startswith("hogwild")
                   else ctx.iters.value)
    final = float(eval_fn(v.center.copy()))
    history.append((total_time, total_iters, final))
    trace = _collect_local_trace(cfg, tr.name, P) if cfg.trace else None
    counters = {"sync_rounds": ctx.sync_rounds.value,
                "messages": ctx.messages.value,
                "wire_bytes": ctx.wire_bytes.value}
    health = None
    if live is not None:
        live.sample(gauges=_live_gauges())   # final sample at end state
        health = live.health()
        counters["health_events"] = len(health["events"])
        live.close()
    return PSResult(
        algorithm=cfg.algorithm, transport=cfg.transport,
        schedule=sched_name if cfg.algorithm in SYNC else "master",
        history=history, total_time_s=total_time, total_iters=total_iters,
        counters=counters,
        final_metric=final, center=v.center.copy(),
        workers=v.workers_w.copy(), trace=trace, health=health)


def _collect_local_trace(cfg: PSConfig, transport: str, P: int):
    """Gather worker/comm tracers after a thread or process run and merge
    them (offsets are 0: perf_counter is system-wide on one host). Thread
    transport reads the registry; process transport reads the spill files
    the workers wrote on exit. The comm executor's tracer (wid=-1) rides
    as the 'master' plane, mirroring where the exchange runs on tcp."""
    workers: dict = {}
    master_threads: dict = {}
    if transport == "thread":
        for t in obs_trace.drain():
            if t.wid >= 0:
                workers.setdefault(t.wid, {"threads": {}, "dropped": 0})
                workers[t.wid]["threads"][t.name] = t.spans()
                workers[t.wid]["dropped"] += t.dropped
            else:
                master_threads[t.name] = t.spans()
    else:
        for t in obs_trace.drain():          # launcher-side tracers (comm)
            if t.wid < 0:
                master_threads[t.name] = t.spans()
        for wid in range(P):
            path = obs_trace.spill_path(cfg.trace_dir, wid)
            if os.path.exists(path):
                workers[wid] = obs_trace.load_spill(path)
    merged = obs_report.merge_traces(
        workers, {"threads": master_threads} if master_threads else None)
    merged["report"] = obs_report.breakdown(merged)
    return merged


# ---------------------------------------------------------------------------
# DES calibration — so simulated and measured clocks are comparable
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Calibration:
    """Micro-benchmarked machine constants for DES↔real comparison.

    ``t_grad_serial`` — one gradient on an otherwise idle box;
    ``t_grad_concurrent`` — a worker's per-gradient WALL period when all P
    workers run at once on this transport (measured with real threads /
    real processes: GIL, caches, and cgroup CPU quotas included);
    ``t_axpy`` / ``alpha`` — shared-memory 'wire' bandwidth and
    small-message overhead; ``link_alpha`` / ``link_beta`` — the measured
    α–β of the real socket link (tcp transport: loopback or host NIC,
    micro-benchmarked with the repro.net framing itself), reported so the
    DES charges the wire the run actually has.
    """

    n: int
    n_workers: int
    transport: str
    t_grad_serial: float
    t_grad_concurrent: float
    t_axpy: float
    alpha: float
    link_alpha: float = 0.0
    link_beta: float = 0.0
    profile: Optional[costmodel.LinkProfile] = None   # measured per-link-
    #                                  class α–β (cfg.topology runs only):
    #                                  what comm.choose consumes at build
    #                                  time and WELCOME ships to workers

    def sim_config(self, algorithm: str, schedule: str,
                   eval_every_iters: int = 200, seed: int = 0,
                   net: Optional[costmodel.Network] = None,
                   topology: Optional[costmodel.Topology] = None
                   ) -> SimConfig:
        """The DES's per-worker compute time depends on the concurrency
        discipline: original_easgd serializes the whole pipeline (one
        worker computes at a time, at full-core speed — and that is
        exactly what it is criticized for); everyone else runs P workers
        concurrently, so each 'device' delivers a gradient every
        ``t_grad_concurrent``. Pass ``net`` = the run's
        ``PSConfig.emulate_net`` so both clocks charge the same wire;
        default: the measured shared-memory 'network'."""
        if algorithm == "original_easgd":
            t_compute = self.t_grad_serial
        else:
            t_compute = self.t_grad_concurrent
        if topology is None and self.profile is not None:
            topology = self.profile.topology
        if net is None:
            if topology is not None:
                # topology runs pace on the declared link classes (the
                # measured profile = declared + physical floor), so the
                # DES must charge intra — the raw loopback link would
                # undercharge a UNIFORM topology by the whole emulation
                net = topology.intra
            else:
                net = (costmodel.Network("tcp-link", self.link_alpha,
                                         self.link_beta)
                       if self.transport == "tcp" and self.link_alpha
                       else costmodel.Network("shm", self.alpha,
                                              self.t_axpy / (self.n * 8)))
        return SimConfig(
            n_workers=self.n_workers,
            net=net,
            schedule=schedule,
            t_compute=t_compute,
            compute_jitter=0.0,
            t_update_per_byte=self.t_axpy / (self.n * 8),
            eval_every_iters=eval_every_iters,
            seed=seed,
            topology=topology)


def _tcp_concurrent_rate(problem, P: int, samples: int) -> float:
    """Median per-gradient wall period across P jax-free worker
    interpreters running at once (``repro.net.worker --burn``). The stdin
    gate excludes interpreter startup + problem build from the clock."""
    import json
    import subprocess
    import sys as _sys

    from repro.net.server import worker_env
    env = worker_env()
    spec_json = json.dumps({"factory": problem.factory,
                            "kwargs": list(problem.kwargs)})
    procs = [subprocess.Popen(
        [_sys.executable, "-m", "repro.net.worker", "--wid", str(i),
         "--burn", spec_json, "--samples", str(samples)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
        for i in range(P)]
    try:
        for pr in procs:
            assert pr.stdout.readline().strip() == "R"   # built + warm
        for pr in procs:
            pr.stdin.write("go\n")
            pr.stdin.flush()
        periods = [float(pr.stdout.readline()) for pr in procs]
    finally:
        for pr in procs:
            pr.stdin.close()
            pr.wait()
    return float(np.median(periods))


def _process_burner(problem, samples, wid, gate):
    """Module-level so spawn can pickle it (process calibration)."""
    w0, grad_fn, _ = problem.build()
    w = np.asarray(w0, np.float64).copy()
    for k in range(5):                       # warmup: imports, pages, caches
        grad_fn(w, k, -(wid + 2))
    gate.wait()
    for k in range(samples):
        grad_fn(w, k, -(wid + 2))


def calibrate(problem, cfg: PSConfig, samples: int = 10) -> Calibration:
    """Measure this box. Calibration gradients use worker ids ≤ −1
    (private RNG streams), so a subsequent measured run's per-worker
    streams are untouched."""
    built = problem.build() if hasattr(problem, "build") else problem
    w0, grad_fn, _ = built
    w = np.asarray(w0, np.float64).copy()
    n, P = w.size, cfg.n_workers
    grad_fn(w, 0, -1)                        # warmup
    t = time.perf_counter()
    for k in range(samples):
        grad_fn(w, k, -1)
    t_serial = (time.perf_counter() - t) / samples

    if cfg.transport == "thread":
        # threads share one GIL: measure the real concurrent rate
        def _burn(wid):
            wl = w.copy()
            for k in range(samples):
                grad_fn(wl, k, -(wid + 2))
        ths = [threading.Thread(target=_burn, args=(i,)) for i in range(P)]
        t = time.perf_counter()
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        t_concurrent = (time.perf_counter() - t) / samples
    elif cfg.transport == "tcp" and hasattr(problem, "build"):
        # the tcp transport's workers are jax-free, self-paced
        # subprocesses — calibrate with EXACTLY that substrate
        # (repro.net.worker --burn): each burner times its own gradient
        # period while all P run; the median is the concurrent rate
        t_concurrent = _tcp_concurrent_rate(problem, P, samples)
    elif hasattr(problem, "build"):
        # real processes from a gate: spawn/import excluded from the clock
        import multiprocessing
        mp = multiprocessing.get_context("spawn")
        gate = mp.Barrier(P + 1)
        procs = [mp.Process(target=_process_burner,
                            args=(problem, samples, i, gate), daemon=True)
                 for i in range(P)]
        for pr in procs:
            pr.start()
        gate.wait()
        t = time.perf_counter()
        for pr in procs:
            pr.join()
        t_concurrent = (time.perf_counter() - t) / samples
    else:
        ncores = os.cpu_count() or 1
        t_concurrent = t_serial * max(1.0, P / ncores)

    big, src = np.zeros(n), np.ones(n)
    t = time.perf_counter()
    for _ in range(10):
        big += 0.5 * src
    t_axpy = (time.perf_counter() - t) / 10
    tiny_dst, tiny_src = np.zeros(64), np.ones(64)
    t = time.perf_counter()
    for _ in range(100):
        np.copyto(tiny_dst, tiny_src)
    alpha = (time.perf_counter() - t) / 100 + 15e-6   # + wakeup allowance
    link_alpha = link_beta = 0.0
    if cfg.transport == "tcp":
        # the REAL α–β of the socket link (loopback here; the host NIC on a
        # real cluster), measured through the repro.net framing itself —
        # this is what the DES charges when no wire is emulated
        from repro.net.wire import measure_link
        link_alpha, link_beta = measure_link(cfg.tcp_host)
    profile = None
    if cfg.topology is not None:
        profile = measured_link_profile(
            cfg, base=(link_alpha, link_beta) if link_alpha else None)
    return Calibration(n=n, n_workers=P, transport=cfg.transport,
                       t_grad_serial=t_serial, t_grad_concurrent=t_concurrent,
                       t_axpy=t_axpy, alpha=alpha,
                       link_alpha=link_alpha, link_beta=link_beta,
                       profile=profile)


def measured_link_profile(cfg: PSConfig, counters=None,
                          base: Optional[tuple] = None
                          ) -> costmodel.LinkProfile:
    """Learn a per-link-class α–β profile from the live machinery.

    The physical floor comes from a short pairwise burst over the real
    substrate — ``net.wire.measure_link`` frames small-RTT + one-way-bulk
    probes through the actual repro.net framing for tcp, a timed memcpy
    for the thread plane (its 'wire' is shared memory). A traced run's
    ``counters['link_alpha_s']`` (clock-probe rtt/2 per master link)
    overrides the burst α when present. The floor composes ADDITIVELY
    with the emulated topology classes: pacing sleeps ride on top of real
    transfer, so measured-α + class-α is the honest per-message estimate
    (an upper bound when the OS overlaps them). ``base`` short-circuits
    the burst with an already-measured (α, β) pair."""
    topo = cfg.topology
    assert topo is not None, "measured_link_profile needs cfg.topology"
    detail: dict = {}
    if base is not None:
        alpha0, beta0 = base
        source = f"measured:{cfg.transport}"
    elif cfg.transport == "tcp":
        from repro.net.wire import measure_link
        alpha0, beta0 = measure_link(cfg.tcp_host, reps=12,
                                     big_bytes=1_000_000)
        source = "measured:tcp"
    else:
        buf, src = np.zeros(1 << 17), np.ones(1 << 17)
        np.copyto(buf, src)                       # warm pages
        t0 = time.perf_counter()
        for _ in range(8):
            np.copyto(buf, src)
        beta0 = (time.perf_counter() - t0) / 8 / buf.nbytes
        tiny_d, tiny_s = np.zeros(64), np.ones(64)
        t0 = time.perf_counter()
        for _ in range(100):
            np.copyto(tiny_d, tiny_s)
        alpha0 = (time.perf_counter() - t0) / 100
        source = "measured:thread"
    detail["alpha0_s"] = float(alpha0)
    detail["beta0_s_per_byte"] = float(beta0)
    probes = (counters or {}).get("link_alpha_s")
    if isinstance(probes, dict) and probes:
        vals = sorted(probes.values())
        alpha0 = float(vals[len(vals) // 2])
        detail["alpha0_s"] = alpha0
        detail["alpha0_source"] = "clock-probe rtt/2 median"
    intra = costmodel.Network(f"{topo.intra.name} +measured",
                              topo.intra.alpha + alpha0,
                              topo.intra.beta + beta0)
    cross = (intra if topo.cross == topo.intra else
             costmodel.Network(f"{topo.cross.name} +measured",
                               topo.cross.alpha + alpha0,
                               topo.cross.beta + beta0))
    measured = costmodel.Topology(hosts=topo.hosts, slots=topo.slots,
                                  intra=intra, cross=cross)
    return costmodel.LinkProfile(topology=measured, source=source,
                                 detail=detail)


def calibrate_sim(problem, cfg: PSConfig, samples: int = 10,
                  eval_every_iters: Optional[int] = None) -> SimConfig:
    """One-call convenience: ``calibrate`` + ``sim_config`` for cfg's own
    algorithm/schedule."""
    cal = calibrate(problem, cfg, samples=samples)
    return cal.sim_config(
        cfg.algorithm, cfg.resolved_schedule(cal.n * 8, profile=cal.profile),
        eval_every_iters=eval_every_iters or cfg.eval_every_iters,
        seed=cfg.seed)


def run_vs_des(problem, easgd: EASGDConfig, cfg: PSConfig,
               cal: Optional[Calibration] = None) -> tuple:
    """THE measured-vs-simulated comparison protocol, in one place (the
    launch CLI and benchmarks/fig6_8 --real both use it): run ``cfg`` for
    real AND through the DES calibrated on the same box, charging the DES
    the run's own emulated wire. Returns (PSResult, RunResult, record) —
    ``record`` is the flat JSON-ready comparison.
    """
    if cal is None:
        cal = calibrate(problem, cfg)
    built = problem.build() if hasattr(problem, "build") else problem
    w0, grad_fn, eval_fn = built
    sched_name = cfg.resolved_schedule(cal.n * 8, profile=cal.profile)
    sim = cal.sim_config(
        cfg.algorithm, sched_name,
        eval_every_iters=cfg.eval_every_iters, seed=cfg.seed,
        net=cfg.emulate_net)
    des = PSEngine(grad_fn, eval_fn, np.asarray(w0, np.float64), easgd,
                   sim).run(cfg.algorithm, total_iters=cfg.total_iters)
    if cal.profile is not None and cfg.link_profile is None:
        # the measured run must consume the SAME profile the chooser and
        # the DES just priced — build-time choice, not a fresh guess
        cfg = dataclasses.replace(cfg, link_profile=cal.profile)
    res = run_ps(problem, easgd, cfg)
    meas = res.total_time_s / max(res.total_iters, 1)
    pred = des.total_time_s / max(des.total_iters, 1)
    record = {
        "algorithm": cfg.algorithm,
        "transport": cfg.transport,
        "schedule": res.schedule,
        "iters": res.total_iters,
        "measured_us_per_iter": 1e6 * meas,
        "des_us_per_iter": 1e6 * pred,
        "measured_over_des": meas / pred,
        "iters_per_sec": 1.0 / meas,
        "final_err": res.final_metric,
        "counters": res.counters,
        "curve_real": [(round(t, 4), it, e) for t, it, e in res.history],
        "curve_des": [(round(t, 4), it, e) for t, it, e in des.history],
    }
    if cal.profile is not None:
        record["profile_source"] = cal.profile.source
        record["profile_detail"] = dict(cal.profile.detail)
    return res, des, record
