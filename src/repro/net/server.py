"""The repro.net master: the PS runtime's concurrency disciplines served
over TCP connections instead of shared memory.

The state layout is the thread transport's, verbatim (center, per-worker
weights/velocities, the padded allreduce mailbox); what changes is WHO moves
the bytes. Shared memory made publication implicit — here every exchange is
an explicit frame on a link, so the master OWNS all optimizer state and the
workers hold only what they need to compute gradients:

 * ``original_easgd`` — the master serves one worker at a time end to end
   (sends WEIGHTS only to the worker whose turn it is, waits for its GRAD):
   the Θ(P) serialization is enforced by the wire itself.
 * async FCFS — GRAD frames are absorbed in ARRIVAL order; under
   ``deterministic=True`` arrivals are buffered per worker and absorbed in
   strict cyclic order — the DES zero-jitter event schedule, which makes
   TCP-vs-thread weights BITWISE identical (tests/test_net.py).
 * hogwild — absorb on arrival with no admission discipline at all. A
   central server linearizes updates at message granularity, so TCP hogwild
   is the DES's sequential-consistency model rather than the shared-memory
   transports' torn writes (see DESIGN.md §net — the honest boundary).
 * sync family — per training round the master distributes WEIGHTS, runs
   the registered schedule's ``Schedule.rounds`` over its local mailbox
   (same numpy executor as the thread transport ⇒ same summation order ⇒
   same bits) while the workers' gradient computation genuinely overlaps
   (paper §6.1.3), then absorbs the GRADs and applies the center update.
   Under ``PSConfig.sync_plane="p2p"`` the master instead degrades to a
   CONTROL-PLANE coordinator: WELCOME ships the peer directory + the
   resolved rounds, the workers execute them over direct worker↔worker
   links (``net.peer``) and advance bitwise-identical center replicas,
   and the master links carry only worker 0's CENTER reports at eval
   rounds plus one final WSTATE per worker — Θ(N_center) instead of the
   centralized plane's Θ(P·N) per round (see DESIGN.md §net).

τ>1 communication periods: workers take τ−1 local steps
(``easgd_flat.local_step``) between exchanges, so their local (w, v)
diverge from the master's copy; the exchange frame then stacks [grad|w|v]
(async) or sends a WSTATE frame ahead of the overlap (sync).

Wire emulation (``PSConfig.emulate_net``) composes with the real socket:
deadlines are taken BEFORE a transfer and slept to AFTER it, so only the
excess over the measured link is slept, and the emulated α–β floors the
real one. Pacing prices the POST-compression payload size, so ``sign_ef``
on the wire shortens emulated time as well as measured bytes.
"""
from __future__ import annotations

import heapq
import os
import queue
import socket
import subprocess
import sys
import threading
import time

import numpy as np

from repro.comm import rounds as comm_rounds
from repro.comm import schedules as comm_schedules
from repro.core import costmodel, easgd_flat
from repro.core.compression import sign_ef_wire_nbytes
from repro.ft import chaos as ft_chaos
from repro.ft import membership as ft_membership
from repro.net import wire
from repro.net.wire import Link, sleep_until
from repro.obs import live as obs_live
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.ps.runtime import (PSResult, execute_rounds,
                              measured_link_profile)

SYNC = easgd_flat.SYNC_FAMILY
DEFAULT_TOKEN = "repro-net"


def wire_payload_nbytes(n_elements: int, codec: str) -> int:
    """Exact framed payload size of one n-element array message."""
    if codec == "sign_ef":
        return sign_ef_wire_nbytes(n_elements)
    return n_elements * 8


def worker_env(pallas: bool = False) -> dict:
    """Environment for a spawned worker interpreter: the repo's src dir on
    PYTHONPATH (shared by the training spawn and the calibration burners —
    one definition of how a worker process is launched). A worker never
    holds an accelerator: a chip belongs to one process, and the parent
    may be that process, so every child is pinned to the CPU backend.
    ``pallas`` also pins the XLA CPU backend to a no-FMA ISA BEFORE the
    child's first jax import, so the fused elastic-update kernel stays
    bitwise equal to easgd_flat (see kernels/elastic_update.py)."""
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    if pallas:
        env.setdefault("XLA_FLAGS", "--xla_cpu_max_isa=SSE4_2")
    return env


def cluster_spec_env(role: str, wid: int, host: str, port: int,
                     token: str = DEFAULT_TOKEN,
                     sync_plane: str | None = None,
                     peer_port: int | None = None) -> str:
    """The declarative ``REPRO_CLUSTER_SPEC`` JSON one process needs to
    (re)join a run: a respawn is a re-exec of ``python -m repro.net.worker``
    with this env var set (plus ``--rejoin``), not a hand-crafted command
    line. launch/cluster prints the same spec for multi-host workers."""
    import json as _json
    spec = {"role": role, "wid": wid, "host": host, "port": int(port),
            "token": token}
    if sync_plane is not None:
        spec["sync_plane"] = sync_plane
    if peer_port is not None:
        spec["peer_port"] = int(peer_port)
    return _json.dumps(spec)


def spawn_local_workers(host: str, port: int, n_workers: int,
                        token: str = DEFAULT_TOKEN,
                        pallas: bool = False,
                        env_extra: dict | None = None) -> list:
    """Launch localhost worker processes (fresh interpreters — the same
    isolation a remote host gives, minus the cable). Each child also gets a
    ``REPRO_CLUSTER_SPEC`` describing its own role, so a respawn is a
    re-exec; ``env_extra`` carries run-scoped injections (REPRO_CHAOS)."""
    base = worker_env(pallas=pallas)
    if env_extra:
        base.update(env_extra)
    procs = []
    for i in range(n_workers):
        env = dict(base)
        env["REPRO_CLUSTER_SPEC"] = cluster_spec_env(
            "worker", i, host, port, token)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.net.worker",
             "--connect", f"{host}:{port}", "--wid", str(i),
             "--token", token],
            env=env))
    return procs


def worker_command(addr: str, wid: int, token: str = DEFAULT_TOKEN,
                   sync_plane: str | None = None,
                   peer_port: int | None = None) -> str:
    """The shell line a REMOTE host runs to join this master (printed by
    launch/cluster for --hosts; also what --ssh executes). For a p2p run
    the line pins the worker's peer-listener port (``--peer-port``) so the
    worker↔worker data plane is firewall-predictable, and carries
    ``--sync-plane`` so the one-liner is launchable verbatim."""
    cmd = (f"PYTHONPATH=src python -m repro.net.worker "
           f"--connect {addr} --wid {wid} --token {token}")
    if sync_plane is not None:
        cmd += f" --sync-plane {sync_plane}"
    if peer_port is not None:
        cmd += f" --peer-port {peer_port}"
    return cmd


class MasterServer:
    """One training run: rendezvous P links, run the discipline, shut down."""

    def __init__(self, problem, easgd, cfg, eval_fn_override=None,
                 join_timeout_s: float = 600.0):
        if not hasattr(problem, "build"):
            raise ValueError(
                "tcp transport needs a ProblemSpec (module:function) — "
                "remote workers rebuild the problem from its factory")
        if cfg.deterministic and cfg.wire_compression != "none":
            raise ValueError(
                "deterministic admission is the bitwise DES/thread "
                "cross-check mode; lossy wire compression "
                f"('{cfg.wire_compression}') would break it — run one or "
                "the other")
        self.problem = problem
        self.easgd = easgd
        self.cfg = cfg
        self.timeout = join_timeout_s
        w0, grad_fn, eval_fn = problem.build()
        self.eval_fn = eval_fn_override or eval_fn
        self.w0 = np.asarray(w0, np.float64)
        self.n = self.w0.size
        P = cfg.n_workers
        self.tau = max(int(getattr(easgd, "tau", 1)), 1)
        # heterogeneous fabric: the topology prices every pacing sleep per
        # link class; with schedule="auto" and no profile supplied, measure
        # one NOW (short pairwise burst over the real substrate) so the
        # choice below ranks candidates on the fabric the run actually has
        self.topology = getattr(cfg, "topology", None)
        self.profile = getattr(cfg, "link_profile", None)
        if (self.topology is not None and self.profile is None
                and cfg.schedule == "auto"):
            self.profile = measured_link_profile(cfg)
        self.sched_name = cfg.resolved_schedule(self.n * 8,
                                                profile=self.profile)
        self.rounds = (comm_schedules.get(self.sched_name)
                       .rounds(P, self.n * 8, cfg.net,
                               topology=self.topology)
                       if cfg.algorithm in SYNC else [])
        self.sync_p2p = (cfg.algorithm in SYNC
                         and getattr(cfg, "sync_plane", "master") == "p2p")
        if self.sync_p2p and any(
                m.src == comm_schedules.MASTER or m.dst == comm_schedules.MASTER
                for rnd in self.rounds for m in rnd):
            raise ValueError(
                f"schedule '{self.sched_name}' routes through the master "
                f"endpoint — it IS the master plane; pick a peer schedule "
                f"(ring/tree/butterfly/hierarchical) for sync_plane='p2p'")
        padded = self.n + (-self.n) % max(P, 1)
        self.padded = padded
        # layer sizes survive past build so an elastic reconfiguration can
        # re-derive bucket boundaries for the new padded size
        self._layer_sizes = getattr(grad_fn, "layer_sizes", None)
        self.boundaries = None
        if getattr(cfg, "bucket_bytes", 0) > 0 and cfg.algorithm in SYNC:
            self.boundaries = comm_rounds.default_bucket_boundaries(
                self._layer_sizes, padded, cfg.bucket_bytes)
        # -- master-owned optimizer state (thread-transport layout) --------
        self.center = self.w0.copy()
        self.master_vel = np.zeros(self.n)
        self.workers_w = np.tile(self.w0, (P, 1))
        self.workers_v = np.zeros((P, self.n))
        self.mailbox = np.zeros((P + 1, padded))
        # -- wiring --------------------------------------------------------
        # master_link_bytes counts ONLY frames on the master's own links
        # (wire_bytes additionally absorbs the local-mailbox round bytes of
        # the centralized sync plane) — the p2p-vs-master incast comparison
        # reads this slot on both planes. The registry replaces the old
        # parallel counter dicts: one namespace, same ``.value`` cells.
        self.counters = obs_metrics.Registry()
        for name in ("sync_rounds", "messages", "wire_bytes",
                     "master_link_bytes"):
            self.counters.counter(name)
        self.link_counters = {"messages": self.counters["messages"],
                              "wire_bytes": self.counters["wire_bytes"],
                              "link_bytes": self.counters["master_link_bytes"]}
        if cfg.trace:
            obs_trace.drain()                # clean registry for THIS run
        self.tracer = (obs_trace.tracer("serve") if cfg.trace else None)
        self.links: dict[int, Link] = {}
        self.peer_addrs: dict[int, list] = {}
        self.bye_stats: dict[int, dict] = {}
        self.events: queue.Queue = queue.Queue()
        self.grad_bufs = [np.zeros(self._up_elems()) for _ in range(P)]
        self.wstate_bufs = [np.zeros(self.n) for _ in range(P)]
        self.iters = 0
        self.history: list = []
        self._last_eval = 0
        self._t0 = 0.0
        self._err: list = []
        self._closing = threading.Event()
        self._threads: list = []
        self._procs: list = []
        self.live = None                 # obs.live.LiveMonitor (telemetry)
        self._draining = False           # True once DONE went out: BYE is
        #                                  then the expected shutdown frame,
        #                                  not a mid-run departure
        # -- elastic membership (ft.membership) ----------------------------
        self.elastic = bool(getattr(cfg, "elastic", False))
        self.membership = (ft_membership.MembershipTable(P)
                           if self.elastic else None)
        self._serving = False            # member_lost conversion applies
        #                                  only once the disciplines run —
        #                                  a rendezvous death still raises
        self._elastic_events: list = []  # lifecycle record when telemetry
        #                                  (and therefore LiveMonitor) is off
        self._proc_reported: set = set()
        self._epoch_round_base = 0       # p2p iteration accounting across
        self._epoch_iters_base = 0       # epochs: iters(k) = base_iters +
        self._epoch_p = P                # (k − base_round) · P_epoch · τ

    # -- payload shapes ------------------------------------------------------

    def _up_elems(self) -> int:
        """Element count of one GRAD frame: with τ>1 the async families
        stack [grad|w] (+[v] for the velocity rules) because the worker's
        local state diverged between exchanges."""
        if self.tau == 1 or self.cfg.algorithm in SYNC:
            return self.n
        k = 3 if easgd_flat.uses_velocity(self.cfg.algorithm) else 2
        return k * self.n

    def _split_up(self, wid: int):
        """(grad, w_up, v_up) views of a received GRAD payload."""
        buf = self.grad_bufs[wid]
        if buf.size == self.n:
            return buf, None, None
        parts = buf.reshape(-1, self.n)
        return parts[0], parts[1], (parts[2] if parts.shape[0] == 3 else None)

    @property
    def _down_stacked(self) -> bool:
        """τ>1 velocity rules evolve V locally between exchanges, so the
        master's WEIGHTS frame must carry [w|v] down."""
        return (self.tau > 1 and self.cfg.algorithm not in SYNC
                and easgd_flat.uses_velocity(self.cfg.algorithm))

    def _absorb_upload(self, wid: int) -> np.ndarray:
        """Fold a τ>1 upload back into the master's per-worker state and
        return the gradient."""
        grad, w_up, v_up = self._split_up(wid)
        if w_up is not None:
            self.workers_w[wid] = w_up
        if v_up is not None:
            self.workers_v[wid] = v_up
        return grad

    def _down_elems(self) -> int:
        return 2 * self.n if self._down_stacked else self.n

    def _up_segments(self) -> int:
        """Logical segments of a GRAD frame (per-segment sign-EF scales)."""
        return self._up_elems() // self.n

    # -- pacing --------------------------------------------------------------

    def _t_msg_pair(self, wid: int | None = None) -> tuple:
        """(t_down, t_up) emulated per-message times — the two directions
        differ in size once τ>1 stacks state into the frames. ``wid``
        applies that worker's ``PSConfig.link_slow`` stretch: a controlled
        per-link straggler on the pacing plane only (admission order and
        math are untouched — the DETECTOR must find it, not the iterates)."""
        codec = self.cfg.wire_compression
        slow = self.cfg.link_slow_factor(wid) if wid is not None else 1.0
        if self.topology is not None:
            # master links ride the topology's class for (MASTER, wid):
            # cross-host whenever hosts > 1 — the master is its own box
            link = self.topology.link(comm_rounds.MASTER,
                                      0 if wid is None else wid)
            return (slow * costmodel.t_msg(
                        wire_payload_nbytes(self._down_elems(), codec),
                        link),
                    slow * costmodel.t_msg(
                        wire_payload_nbytes(self._up_elems(), codec),
                        link))
        return (slow * self.cfg.t_msg_emulated(
                    wire_payload_nbytes(self._down_elems(), codec)),
                slow * self.cfg.t_msg_emulated(
                    wire_payload_nbytes(self._up_elems(), codec)))

    # -- sync-family round arithmetic (shared by both planes) ---------------

    def _n_sync_rounds(self) -> int:
        return -(-self.cfg.total_iters // (self.cfg.n_workers * self.tau))

    def _t_sync_wire(self, wid: int | None = None) -> float:
        """Emulated α–β time of one full exchange: the rounds serialize,
        each costs α + max_frac·n·β (its messages fly concurrently). With
        a topology each message is priced over ITS link class, and ``wid``
        restricts to that worker's own segments — its personal deadline on
        a heterogeneous mesh (intra-host pairs finish early and wait on
        cross-host peers at the blocking recv, not by sleeping)."""
        if self.topology is not None:
            return comm_rounds.t_rounds(self.rounds, self.n * 8,
                                        topology=self.topology, wid=wid)
        return sum(
            self.cfg.t_msg_emulated(max(m.frac for m in rnd) * self.n * 8)
            for rnd in self.rounds)

    def _t_sync_wire_buckets(self, wid: int | None = None) -> list:
        """Per-bucket emulated wire time: under bucketing each round
        fragments into per-bucket frames, so bucket b pays α + its own
        max clipped span·β for every round it appears in. Σ_b can exceed
        ``_t_sync_wire`` (more frames ⇒ more α) — that extra latency is
        exactly what the overlap pipeline is for. Topology/``wid`` as in
        ``_t_sync_wire``: per-link-class, per-worker SEGMENT pacing."""
        if self.topology is not None:
            return comm_rounds.t_rounds_buckets(
                self.rounds, self.padded, self.boundaries,
                topology=self.topology, wid=wid)
        plans = comm_rounds.bucket_rounds(self.rounds, self.padded,
                                          self.boundaries)
        out = []
        for plan in plans:
            t = 0.0
            for rnd in plan:
                if rnd:
                    t += self.cfg.t_msg_emulated(
                        max(b - a for _, (a, b) in rnd) * 8)
            out.append(t)
        return out

    def _eval_rounds(self) -> list:
        """Exchange-round indices after which the eval cadence fires —
        the `_maybe_eval` trigger precomputed, so the p2p workers and this
        master agree on exactly when worker 0 reports its CENTER."""
        evals, last = [], 0
        per = self.cfg.n_workers * self.tau
        for k in range(self._n_sync_rounds()):
            if (k + 1) * per - last >= self.cfg.eval_every_iters:
                evals.append(k)
                last = (k + 1) * per
        return evals

    # -- lifecycle -----------------------------------------------------------

    def _welcome_payload(self, wid: int, rejoin: bool = False) -> dict:
        """One worker's WELCOME: problem spec + algorithm, plus the full p2p
        geometry when the data plane is peer-to-peer. A rejoin WELCOME names
        the CURRENT epoch's geometry but the worker holds off joining the
        mesh until the RECONFIGURE that folds it in (``rejoin`` flag)."""
        cfg, e = self.cfg, self.easgd
        welcome = {
            "wid": wid,
            "factory": self.problem.factory,
            "kwargs": list(self.problem.kwargs),
            "algorithm": cfg.algorithm,
            "n": self.n,
            "tau": self.tau,
            "eta": e.eta, "mu": e.mu, "rho": e.rho,
            "codec": cfg.wire_compression,
            "warmup": 2,
            "hb_interval_s": cfg.hb_interval_eff_s(),
            "trace": bool(cfg.trace),
            "trace_dir": cfg.trace_dir,
        }
        if self.topology is not None:
            welcome["topology"] = self.topology.to_wire()
        if self.profile is not None:
            welcome["link_profile"] = self.profile.to_wire()
        if self.sync_p2p:
            # a link_slow worker paces ITS exchange deadlines slower —
            # the mesh is lockstep, so its lag surfaces in every
            # worker's clock, but its own heartbeat telemetry is what
            # names it
            slow = cfg.link_slow_factor(wid)
            welcome.update({
                "sync_plane": "p2p",
                "p": len(self.links) if rejoin else cfg.n_workers,
                "padded": self.padded,
                "rounds": comm_schedules.rounds_to_wire(self.rounds),
                "n_rounds": self._n_sync_rounds(),
                "eval_rounds": self._eval_rounds(),
                "t_wire_s": slow * self._t_sync_wire(
                    wid if self.topology is not None else None),
                "peers": {str(w): a for w, a in self.peer_addrs.items()},
                "bucket_bounds": self.boundaries,
                "overlap": getattr(cfg, "overlap", True),
                "update_backend": getattr(cfg, "update_backend",
                                          "numpy"),
                "t_wire_bucket_s": ([slow * t for t in
                                     self._t_sync_wire_buckets(
                                         wid if self.topology is not None
                                         else None)]
                                    if self.boundaries else []),
                "elastic": self.elastic,
            })
        if rejoin:
            welcome["rejoin"] = True
        return welcome

    def rendezvous(self, listener: socket.socket, token: str) -> None:
        """Accept until every wid 0..P−1 has said HELLO, send WELCOME, wait
        for every READY (worker built its problem and warmed up)."""
        cfg, P = self.cfg, self.cfg.n_workers
        deadline = time.monotonic() + self.timeout
        listener.settimeout(1.0)
        while len(self.links) < P:
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"rendezvous timeout: {len(self.links)}/{P} workers "
                    f"connected (algorithm={cfg.algorithm})")
            self._check_procs()
            try:
                conn, _ = listener.accept()
            except socket.timeout:
                continue
            conn.settimeout(30.0)       # a connected-but-silent client must
            link = Link(conn, codec=cfg.wire_compression,   # not stall HELLO
                        counters=self.link_counters)
            try:
                frame = link.recv_header()
            except (socket.timeout, wire.WireError, OSError):
                link.close()
                continue
            if frame.ftype != wire.HELLO:
                link.close()
                continue
            hello = link.recv_json(frame)
            if hello.get("token") != token:
                link.send_json(wire.ERROR, {"msg": "bad token"})
                link.close()
                continue
            wid = int(hello["wid"])
            if not (0 <= wid < P) or wid in self.links:
                link.send_json(wire.ERROR, {"msg": f"bad wid {wid}"})
                link.close()
                continue
            if "peer" in hello:
                self.peer_addrs[wid] = list(hello["peer"])
            self.links[wid] = link
        if self.sync_p2p:
            missing = [w for w in self.links if w not in self.peer_addrs]
            if missing:
                for link in self.links.values():
                    link.send_json(wire.ERROR, {
                        "msg": f"sync_plane=p2p but worker(s) {missing} "
                               f"advertised no peer listener "
                               f"(started with --sync-plane master?)"})
                raise RuntimeError(
                    f"p2p rendezvous failed: worker(s) {missing} advertised "
                    f"no peer listener")
        for wid, link in self.links.items():
            link.send_json(wire.WELCOME, self._welcome_payload(wid))
        for wid, link in self.links.items():
            self._threads.append(threading.Thread(
                target=self._reader, args=(wid, link), daemon=True))
            self._threads[-1].start()
        ready = set()
        while len(ready) < P:
            wid, kind, detail = self._next_event(deadline - time.monotonic())
            if kind != "ready":
                raise RuntimeError(
                    f"worker {wid} failed during rendezvous: {kind} {detail}")
            ready.add(wid)
            if self.membership is not None:
                self.membership.mark_ready(wid)

    def _reader(self, wid: int, link: Link) -> None:
        """Per-link reader: decodes frames into per-worker buffers and turns
        them into events. One outstanding exchange per worker by protocol,
        so the preallocated buffers are never overwritten early."""
        try:
            while True:
                frame = link.recv_header()
                if frame.ftype == wire.GRAD:
                    link.recv_array(frame, self.grad_bufs[wid])
                    self.events.put((wid, "grad", None))
                elif frame.ftype == wire.WSTATE:
                    link.recv_array(frame, self.wstate_bufs[wid])
                    self.events.put((wid, "wstate", None))
                elif frame.ftype == wire.CENTER:
                    # the header wid field carries the report tag (eval
                    # round index ≥ 0, −1 final, −2 reconfigure state
                    # upload); the fresh array keeps a slow eval from
                    # racing the next report into a shared buffer
                    self.events.put((wid, "center",
                                     (frame.wid,
                                      link.recv_array(frame).copy())))
                elif frame.ftype == wire.RECONFIGURE:
                    # a survivor acking phase 1 with its completed round
                    self.events.put((wid, "reconf_ack",
                                     link.recv_json(frame)))
                elif frame.ftype == wire.READY:
                    link.recv_discard(frame)
                    self.events.put((wid, "ready", None))
                elif frame.ftype == wire.CLOCK:
                    # NTP-style probe: echo this side's clock immediately —
                    # answered on the reader thread so serve() never blocks
                    # a probe behind an exchange (that would inflate rtt)
                    link.recv_discard(frame)
                    link.send_json(wire.CLOCK,
                                   {"t": time.perf_counter()}, wid=wid)
                elif frame.ftype == wire.BYE:
                    if frame.size:      # p2p workers attach per-link stats
                        self.bye_stats[wid] = link.recv_json(frame)
                    else:
                        link.recv_discard(frame)
                    self.events.put((wid, "bye", None))
                    return
                elif frame.ftype == wire.ERROR:
                    msg = link.recv_json(frame)
                    self.events.put((wid, "error", msg.get("msg", "?")))
                    return
                else:
                    link.recv_discard(frame)
        except (wire.WireError, OSError) as exc:
            if not self._closing.is_set():
                self.events.put((wid, "dead", repr(exc)))

    def _check_procs(self) -> None:
        for i, proc in enumerate(self._procs):
            rc = proc.poll()
            if rc in (None, 0):
                continue
            if self.elastic and self._serving:
                # under elastic membership a nonzero exit is a membership
                # signal, not a run-killer: surface it as a dead event once
                # (the reader's socket-drop event usually beats this poll)
                if (not self.membership.is_lost(i)
                        and i not in self._proc_reported):
                    self._proc_reported.add(i)
                    self.events.put((i, "dead", f"process exited {rc}"))
                continue
            raise RuntimeError(
                f"tcp worker process exited with code {rc} "
                f"(algorithm={self.cfg.algorithm})")

    def _mark_event(self, wid: int, kind: str, detail: str = "") -> None:
        """One lifecycle record: through the LiveMonitor when telemetry is
        on (events + JSONL + health counters), into the local log always —
        PSResult.health must name the death/recovery even on a bare run."""
        if self.live is not None:
            ev = self.live.mark_worker_event(wid, kind, detail)
        else:
            ev = {"t": round(time.monotonic() - (self._t0 or
                                                 time.monotonic()), 3),
                  "kind": kind, "wid": wid,
                  **({"detail": detail} if detail else {})}
        self._elastic_events.append(ev)

    def _member_lost(self, wid: int, kind: str, detail):
        """Elastic conversion of a failure into a membership transition:
        close the link, record the state change, hand the serve loop a
        ``member_lost`` event instead of raising."""
        left = kind == "bye"                       # clean preemption BYE
        if left:
            self.membership.mark_left(wid, "clean BYE mid-run")
        else:
            self.membership.mark_dead(wid, str(detail))
        link = self.links.pop(wid, None)
        if link is not None:
            link.hb_hook = None
            link.close()
        self._mark_event(wid, "worker_left" if left else "worker_dead",
                         str(detail or ""))
        return wid, "member_lost", str(detail or "")

    def _next_event(self, timeout: float):
        """Pop one event; surface worker failures and heartbeat silence as
        RuntimeError instead of hanging the launcher — unless elastic
        membership is on and the disciplines are running, in which case a
        loss becomes a ``member_lost`` event the serve loop absorbs."""
        deadline = time.monotonic() + max(timeout, 0.0)
        absorb = self.elastic and self._serving
        while True:
            self._check_procs()
            if self.links:
                worst = max(time.monotonic() - l.last_seen
                            for l in self.links.values())
                cell = self.counters.gauge("hb_staleness_max_s")
                cell.value = max(cell.value, round(worst, 3))
            hb_timeout = self.cfg.hb_timeout_eff_s()
            stale = [w for w, l in self.links.items()
                     if time.monotonic() - l.last_seen > hb_timeout]
            if stale:
                if absorb:
                    return self._member_lost(
                        stale[0], "dead",
                        f"silent for more than {hb_timeout}s")
                raise RuntimeError(
                    f"worker(s) {stale} silent for more than "
                    f"{hb_timeout}s (heartbeats stopped)")
            try:
                wid, kind, detail = self.events.get(timeout=0.5)
            except queue.Empty:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"timed out waiting for workers "
                        f"(algorithm={self.cfg.algorithm})") from None
                continue
            if kind in ("error", "dead"):
                if absorb and wid in self.links:
                    return self._member_lost(wid, kind, detail)
                if absorb:
                    continue             # duplicate signal for a known loss
                if self.live is not None:
                    self.live.mark_worker_event(wid, "worker_dead",
                                                str(detail))
                raise RuntimeError(f"worker {wid} failed: {detail}")
            if kind == "bye" and not self._draining:
                # a clean mid-run departure (watchdog-triggered SIGTERM →
                # BYE instead of a dead socket): its trace/telemetry flush
                # already landed in bye_stats — surface it as a structured
                # failure naming the worker, not a protocol violation
                if absorb:
                    return self._member_lost(wid, "bye", "preempted")
                if self.live is not None:
                    self.live.mark_worker_event(wid, "worker_left",
                                                "clean BYE mid-run")
                raise RuntimeError(
                    f"worker {wid} left the run (clean BYE mid-run — "
                    f"preempted?)")
            return wid, kind, detail

    def _await(self, kind: str, need: set, ignore: tuple = ()) -> None:
        """Block until every wid in ``need`` delivered one ``kind`` event.
        ``ignore`` lets the shutdown drain skip exchanges that were already
        in flight when DONE went out (their grads are discarded, exactly
        like the shared-memory transports discard a computed-but-unserved
        gradient at termination)."""
        pending = set(need)
        while pending:
            wid, got, _ = self._next_event(self.timeout)
            if got in ignore:
                continue
            if got == "member_lost":     # elastic: the lost worker can no
                pending.discard(wid)     # longer owe us anything
                continue
            if got != kind:
                raise RuntimeError(
                    f"protocol violation: expected {kind} from {pending}, "
                    f"got {got} from worker {wid}")
            pending.discard(wid)

    # -- live telemetry plane (obs.live) -------------------------------------

    def _start_live(self, listener: socket.socket, token: str) -> None:
        """Telemetry on: build the LiveMonitor, point every link's
        heartbeat hook at its store (push — every telemetry-bearing
        HEARTBEAT becomes samples), and start the sampler + STATS-acceptor
        threads. Telemetry off (default) never reaches here: no store, no
        threads, no timestamps — the zero-overhead pin stays intact."""
        cfg = self.cfg
        self.counters.counter("health_events")
        self.live = obs_live.LiveMonitor(
            cfg.n_workers, deadline_factor=cfg.straggler_factor,
            hb_interval_s=cfg.hb_interval_eff_s(),
            jsonl_path=cfg.telemetry_jsonl,
            counters=self.counters,
            meta={"algorithm": cfg.algorithm, "transport": "tcp",
                  "schedule": self.sched_name
                  + ("+p2p" if self.sync_p2p else "")})
        for wid, link in self.links.items():
            link.hb_hook = (lambda payload, w=wid:
                            self.live.ingest_hb(w, payload))
        th = threading.Thread(target=self._live_sampler, daemon=True)
        th.start()
        self._threads.append(th)

    def _live_sampler(self) -> None:
        """Periodic master-side pass: per-link heartbeat age + per-link
        ef_ratio into the store, aggregate gauges under wid −1, one
        detector pass (straggler / hb_stale events). Links are snapshot
        per pass — elastic membership mutates the dict concurrently."""
        period = self.cfg.telemetry_period_s()
        while not self._closing.wait(period):
            now = time.monotonic()
            links = list(self.links.items())
            staleness = {w: round(now - link.last_seen, 3)
                         for w, link in links}
            for w, link in links:
                ratio = link.ef_ratio()
                if ratio is not None:
                    self.live.ingest_hb(w, {"ef_ratio": round(ratio, 2)})
            gauges = {k: v for k, v in self.counters.snapshot().items()
                      if isinstance(v, (int, float))}
            gauges["iters"] = self.iters
            self.live.sample(staleness=staleness, gauges=gauges)

    def _start_acceptor(self, listener: socket.socket, token: str) -> None:
        th = threading.Thread(target=self._control_acceptor,
                              args=(listener, token), daemon=True)
        th.start()
        self._threads.append(th)

    def _control_acceptor(self, listener: socket.socket, token: str) -> None:
        """Post-rendezvous connections on the rendezvous listener: STATS
        snapshot requests from monitors (one request per connection), and —
        under elastic membership — HELLO frames from respawned workers
        rejoining the run (the link is handed to the serve loop as a
        ``rejoin_hello`` event; everything else about admission happens
        there, on the thread that owns the run state)."""
        while not self._closing.is_set():
            try:
                conn, _ = listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return                   # listener closed at shutdown
            client = None
            keep = False
            try:
                conn.settimeout(10.0)
                client = Link(conn, codec=self.cfg.wire_compression,
                              counters=self.link_counters)
                frame = client.recv_header()
                if frame.ftype == wire.STATS and self.live is not None:
                    req = client.recv_json(frame)
                    if req.get("token") != token:
                        client.send_json(wire.ERROR, {"msg": "bad token"})
                        continue
                    client.send_json(
                        wire.STATS,
                        self.live.snapshot(int(req.get("k", 32))))
                    continue
                if frame.ftype == wire.HELLO and self.elastic:
                    hello = client.recv_json(frame)
                    wid = int(hello.get("wid", -1))
                    if hello.get("token") != token:
                        client.send_json(wire.ERROR, {"msg": "bad token"})
                        continue
                    if not (0 <= wid < self.cfg.n_workers):
                        client.send_json(wire.ERROR,
                                         {"msg": f"bad wid {wid}"})
                        continue
                    if wid in self.links or not self.membership.is_lost(wid):
                        client.send_json(wire.ERROR, {
                            "msg": f"wid {wid} is not rejoinable "
                                   f"(state {self.membership.state(wid)})"})
                        continue
                    if not self.sync_p2p:
                        client.send_json(wire.ERROR, {
                            "msg": "rejoin is a p2p sync-plane feature"})
                        continue
                    conn.settimeout(self.timeout)
                    keep = True
                    self.events.put((wid, "rejoin_hello",
                                     {"link": client,
                                      "peer": hello.get("peer")}))
            except (socket.timeout, wire.WireError, OSError, ValueError):
                pass
            finally:
                if not keep:
                    if client is not None:
                        client.close()
                    else:
                        conn.close()

    # -- eval ----------------------------------------------------------------

    def _maybe_eval(self, force: bool = False) -> None:
        if force or self.iters - self._last_eval >= self.cfg.eval_every_iters:
            t0 = time.perf_counter()
            self.history.append((t0 - self._t0, self.iters,
                                 float(self.eval_fn(self.center.copy()))))
            self._last_eval = self.iters
            if self.tracer is not None:
                self.tracer.record(obs_trace.EVAL, t0, time.perf_counter())

    # -- disciplines ---------------------------------------------------------

    def _send_weights(self, wid: int) -> int:
        link = self.links.get(wid)
        if link is None:                 # elastic: lost since we scheduled it
            return 0
        try:
            if self._down_stacked:
                payload = np.concatenate(
                    [self.workers_w[wid], self.workers_v[wid]])
                return link.send_array(wire.WEIGHTS, payload,
                                       wid=wid, segments=2)
            return link.send_array(wire.WEIGHTS, self.workers_w[wid],
                                   wid=wid)
        except (wire.WireError, OSError):
            if self.elastic and self._serving:
                return 0                 # its reader surfaces the loss
            raise

    def serve(self) -> None:
        algo = self.cfg.algorithm
        self._t0 = time.perf_counter()
        self._serving = True             # elastic: losses are now absorbed
        if self.sync_p2p:
            self._serve_sync_p2p()
        elif algo in SYNC:
            self._serve_sync()
        elif algo == "original_easgd":
            self._serve_original()
        elif self.cfg.deterministic:
            self._serve_turnstile()
        elif algo.startswith("hogwild"):
            self._serve_hogwild()
        else:
            self._serve_fcfs()

    def _serve_original(self) -> None:
        """Round-robin with compute-in-turn: WEIGHTS go out only when the
        turn arrives, so the wire itself serializes the whole pipeline.
        Elastic: the rotation runs over the LIVE roster each turn — a lost
        worker simply drops out of the cycle, its turn is re-served."""
        e, cfg = self.easgd, self.cfg
        n_turns = -(-cfg.total_iters // self.tau)
        turn = served = 0
        while served < n_turns:
            roster = sorted(self.links)
            if not roster:
                raise RuntimeError("elastic: every worker was lost")
            j = roster[turn % len(roster)]
            turn += 1
            t_down, t_up = self._t_msg_pair(j)
            deadline = time.monotonic() + t_down
            self._send_weights(j)
            if t_down:
                sleep_until(deadline)            # W̄ down
            self._await("grad", {j})
            if j not in self.links:              # lost while we waited
                continue
            grad = self._absorb_upload(j)
            deadline = time.monotonic() + t_up
            easgd_flat.master_absorb_round_robin(
                self.center, self.workers_w[j], self.workers_v[j], grad, e)
            if t_up:
                sleep_until(deadline)            # W⁽ʲ⁾ up
            served += 1
            self.iters += self.tau
            self._maybe_eval()

    def _serve_turnstile(self) -> None:
        """Deterministic admission: all workers compute ahead, the master
        absorbs in strict cyclic order — the DES zero-jitter event order,
        hence bitwise-identical weights to the thread transport."""
        e, cfg = self.easgd, self.cfg
        ready = [False] * cfg.n_workers
        for wid in self.links:
            self._send_weights(wid)
        turn = 0
        while self.iters < cfg.total_iters:
            j = turn % cfg.n_workers
            t_pair = sum(self._t_msg_pair(j))
            while not ready[j]:
                wid, kind, _ = self._next_event(self.timeout)
                assert kind == "grad", kind
                ready[wid] = True
            ready[j] = False
            deadline = time.monotonic() + t_pair
            grad = self._absorb_upload(j)
            easgd_flat.master_absorb(
                cfg.algorithm, self.center, self.master_vel,
                self.workers_w[j], self.workers_v[j], grad, e)
            if t_pair:
                sleep_until(deadline)
            turn += 1
            self.iters += self.tau
            self._maybe_eval()
            if self.iters < cfg.total_iters:
                self._send_weights(j)

    def _serve_fcfs(self) -> None:
        """Async family: absorb in arrival order; the single master wire
        serializes both messages of each exchange (same ``wire_free_at``
        reservation as the thread transport, slept inline because here the
        master really is the link's endpoint)."""
        e, cfg = self.easgd, self.cfg
        wire_free_at = 0.0
        for wid in self.links:
            self._send_weights(wid)
        while self.iters < cfg.total_iters:
            j, kind, _ = self._next_event(self.timeout)
            if kind == "member_lost":    # elastic: its quota is re-absorbed
                if not self.links:       # by arrival order naturally
                    raise RuntimeError("elastic: every worker was lost")
                continue
            assert kind == "grad", kind
            t_pair = sum(self._t_msg_pair(j))
            deadline = None
            if t_pair:
                start = max(time.monotonic(), wire_free_at)
                deadline = start + t_pair
                wire_free_at = deadline
            grad = self._absorb_upload(j)
            easgd_flat.master_absorb(
                cfg.algorithm, self.center, self.master_vel,
                self.workers_w[j], self.workers_v[j], grad, e)
            if deadline is not None:
                sleep_until(deadline)
            self.iters += self.tau
            self._maybe_eval()
            if self.iters < cfg.total_iters:
                self._send_weights(j)

    def _serve_hogwild(self) -> None:
        """Absorb on arrival, no discipline; per-exchange wire times OVERLAP
        — a delayed-sender thread releases each worker's reply at its own
        deadline, so one worker's wire time never serializes another's
        (the thread transport's lock-free sleep, relocated to the master).
        Per-worker quotas mirror the thread transport's termination."""
        e, cfg = self.easgd, self.cfg
        P, total = cfg.n_workers, cfg.total_iters
        t_pairs = [sum(self._t_msg_pair(w)) for w in range(P)]
        quota = [(total // P + (1 if w < total % P else 0)) for w in range(P)]
        target = [-(-q // self.tau) for q in quota]   # exchanges per worker
        done = [0] * P
        replies: queue.Queue = queue.Queue()          # (deadline, wid)
        stop = threading.Event()

        def _delayed_sender():
            # deadline heap, not FIFO: with per-link pacing (link_slow) a
            # slow worker's long reservation must not head-of-line block
            # the fast workers' short ones — each reply releases at ITS
            # deadline (equal pacing made FIFO coincide with this; unequal
            # pacing does not)
            pend: list = []
            while not stop.is_set():
                timeout = (max(0.0, min(pend[0][0] - time.monotonic(), 0.2))
                           if pend else 0.2)
                try:
                    heapq.heappush(pend, replies.get(timeout=timeout))
                except queue.Empty:
                    pass
                now = time.monotonic()
                while pend and pend[0][0] <= now:
                    _, w = heapq.heappop(pend)
                    self._send_weights(w)

        sender = threading.Thread(target=_delayed_sender, daemon=True)
        sender.start()
        lost_any = False
        try:
            for wid in self.links:
                self._send_weights(wid)
            while any(d < t for d, t in zip(done, target)):
                j, kind, _ = self._next_event(self.timeout)
                if kind == "member_lost":
                    # elastic: forgive the dead worker's remaining quota —
                    # hogwild has no barrier to re-balance, the run just
                    # ends those iterations short
                    target[j] = done[j]
                    lost_any = True
                    if not self.links:
                        raise RuntimeError("elastic: every worker was lost")
                    continue
                assert kind == "grad", kind
                grad = self._absorb_upload(j)
                deadline = time.monotonic() + t_pairs[j]
                easgd_flat.master_absorb(
                    cfg.algorithm, self.center, self.master_vel,
                    self.workers_w[j], self.workers_v[j], grad, e)
                done[j] += 1
                self.iters += self.tau
                self._maybe_eval()
                if done[j] < target[j]:
                    if t_pairs[j]:
                        replies.put((deadline, j))
                    else:
                        self._send_weights(j)
        finally:
            stop.set()
            sender.join(timeout=5)
        if not lost_any:
            self.iters = total                        # quota-exact by design

    def _rebuild_sync_plan(self, p: int) -> None:
        """Elastic membership shrank the centralized sync family to ``p``
        workers: re-resolve dense rounds, padding, bucket boundaries and
        mailbox for P′ — the participation mask realized as geometry. The
        workers are stateless request-reply clients here, so nothing ships
        to them; only the master's exchange plan changes."""
        self.rounds = comm_schedules.get(self.sched_name).rounds(
            p, self.n * 8, self.cfg.net)
        self.padded = self.n + (-self.n) % max(p, 1)
        if getattr(self.cfg, "bucket_bytes", 0) > 0:
            self.boundaries = comm_rounds.default_bucket_boundaries(
                self._layer_sizes, self.padded, self.cfg.bucket_bytes)
        self.mailbox = np.zeros((p + 1, self.padded))
        epoch = self.membership.advance_epoch()
        self.counters.gauge("epoch").value = epoch
        if self.live is not None:
            self.live.set_membership(sorted(self.links))
        self._mark_event(-1, "reconfigure",
                         f"epoch {epoch}: p={p} "
                         f"survivors={sorted(self.links)} (centralized)")

    def _serve_sync(self) -> None:
        """Barriered rounds over links. sync_easgd's allreduce runs on the
        master's mailbox WHILE the workers compute (their gradient follows
        the WEIGHTS/WSTATE they just sent/received) — the §6.1.3 overlap is
        real; sync_sgd's gradient exchange must wait for the GRADs.

        Elastic: each round runs over the live roster — on a loss the
        surviving rows are packed densely, the rounds re-resolved for P′
        and the mean taken over P′ (the participation mask). A worker lost
        AFTER its state entered the mailbox still contributes to that one
        exchange (its grad is simply skipped); it is out of the roster from
        the next round on."""
        e, cfg = self.easgd, self.cfg
        algo, n = cfg.algorithm, self.n
        plan_p = cfg.n_workers
        # the centralized exchange is one barriered pipeline: a slow link
        # slows the whole round, so link_slow stretches the shared pacing
        # by the worst factor (per-worker divergence needs p2p/async)
        t_factor = max(cfg.link_slow) if cfg.link_slow else 1.0
        t_wire = self._t_sync_wire() * t_factor
        tr = self.tracer
        _pc = time.perf_counter
        while self.iters < cfg.total_iters:
            roster = sorted(self.links)
            if not roster:
                raise RuntimeError("elastic: every worker was lost")
            for wid in roster:
                self._send_weights(wid)
            if algo == "sync_easgd":
                got_grad: set = set()
                if self.tau > 1:
                    # workers do τ−1 local steps, then post their evolved
                    # weights (WSTATE) before computing the exchange grad —
                    # the allreduce still overlaps that last computation.
                    # A fast worker's GRAD may arrive before a slow one's
                    # WSTATE, so grads are buffered while we collect.
                    got_w: set = set()
                    need = set(roster)
                    while not need <= got_w:
                        wid, kind, _ = self._next_event(self.timeout)
                        if kind == "member_lost":
                            need.discard(wid)
                            got_grad.discard(wid)
                            continue
                        if kind == "wstate":
                            got_w.add(wid)
                        else:
                            assert kind == "grad", kind
                            got_grad.add(wid)
                    for i in sorted(need):
                        self.workers_w[i] = self.wstate_bufs[i]
                roster = [w for w in roster if w in self.links]
                P = len(roster)
                if P == 0:
                    continue             # everyone died this round
                if P != plan_p:
                    self._rebuild_sync_plan(P)
                    plan_p = P
                    t_wire = self._t_sync_wire() * t_factor
                self.mailbox[:P, :n] = self.workers_w[roster]
                deadline = time.monotonic() + t_wire
                if tr is not None:
                    t0 = _pc()
                execute_rounds(self.mailbox, n, self.rounds, self.counters,
                               boundaries=self.boundaries, tracer=tr)
                if t_wire:
                    sleep_until(deadline)
                if tr is not None:
                    tr.record(obs_trace.EXCHANGE, t0, (t0 := _pc()))
                self._await("grad", set(roster) - got_grad)
                if tr is not None:
                    tr.record(obs_trace.RECV_WAIT, t0, (t0 := _pc()))
                for i in roster:
                    if i in self.links:  # a late loss: skip its local step
                        easgd_flat.worker_step(
                            algo, self.workers_w[i], self.workers_v[i],
                            self.grad_bufs[i], self.center, e)
                easgd_flat.sync_master_easgd(
                    self.center, self.mailbox[0, :n] / P, P, e)
                if tr is not None:
                    tr.record(obs_trace.UPDATE, t0, _pc())
            else:                                     # sync_sgd
                if tr is not None:
                    t0 = _pc()
                self._await("grad", set(roster))
                if tr is not None:
                    tr.record(obs_trace.RECV_WAIT, t0, (t0 := _pc()))
                roster = [w for w in roster if w in self.links]
                P = len(roster)
                if P == 0:
                    continue
                if P != plan_p:
                    self._rebuild_sync_plan(P)
                    plan_p = P
                    t_wire = self._t_sync_wire() * t_factor
                self.mailbox[:P, :n] = [self.grad_bufs[w] for w in roster]
                deadline = time.monotonic() + t_wire
                execute_rounds(self.mailbox, n, self.rounds, self.counters,
                               boundaries=self.boundaries, tracer=tr)
                if t_wire:
                    sleep_until(deadline)
                if tr is not None:
                    tr.record(obs_trace.EXCHANGE, t0, (t0 := _pc()))
                easgd_flat.sync_master_sgd(
                    self.center, self.master_vel, self.mailbox[0, :n] / P, e)
                self.workers_w[:] = self.center
                if tr is not None:
                    tr.record(obs_trace.UPDATE, t0, _pc())
            self.iters += P * self.tau
            self._maybe_eval()

    def _p2p_iters_at(self, k: int) -> int:
        """Total iterations once exchange round ``k`` completes, summed
        across epochs: rounds before the epoch base ran at earlier P's."""
        return (self._epoch_iters_base
                + (k + 1 - self._epoch_round_base) * self._epoch_p
                * self.tau)

    def _p2p_center_report(self, tag: int, payload: np.ndarray) -> bool:
        """Consume one tagged CENTER report. Tag ≥ 0 is an eval report
        after exchange round ``tag``; −1 is the final center. Returns True
        for the final report."""
        n = self.n
        self.center[:] = payload[:n]
        if payload.size >= 2 * n:        # sync_sgd state: [center|vel]
            self.master_vel[:] = payload[n:2 * n]
        if tag >= 0:
            self.iters = self._p2p_iters_at(tag)
            self._maybe_eval(force=True)
            return False
        self.iters = self._p2p_iters_at(self._n_sync_rounds() - 1)
        return True

    def _serve_sync_p2p(self) -> None:
        """The control plane of the p2p sync family: the workers execute
        the rounds among themselves (net/peer.py), so this loop only
        consumes the reporter's CENTER reports (tagged with the exchange
        round in the header's wid field — reports and reconfigurations can
        interleave, so the cadence can't be inferred from arrival order),
        each worker's one final WSTATE, and the heartbeat/error machinery
        of ``_next_event``. No WEIGHTS go out, no GRADs come back: the
        master link moves Θ(N_center), not Θ(P·N) per round.

        Under ``PSConfig.elastic`` this loop is also the membership driver:
        a ``member_lost`` event freezes the superstep and runs
        ``_reconfigure_p2p``; a respawned worker's HELLO (handed over by
        the control acceptor) is admitted here and folded in by another
        reconfiguration once its READY lands."""
        self._epoch_members = set(self.links)
        self._epoch_p = len(self.links)
        final_center = False
        wstates: set = set()
        self._pending_rejoin: list = []
        while not (final_center and wstates >= set(self.links)):
            wid, kind, detail = self._next_event(self.timeout)
            if kind == "center":
                final_center |= self._p2p_center_report(*detail)
            elif kind == "wstate":
                self.workers_w[wid] = self.wstate_bufs[wid]
                wstates.add(wid)
            elif kind == "member_lost":
                if final_center:
                    continue             # already past the last exchange
                self._reconfigure_p2p()
            elif kind == "rejoin_hello":
                if final_center:
                    detail["link"].close()
                else:
                    self._admit_rejoin(wid, detail["link"], detail["peer"])
            elif kind == "ready":
                # a respawned worker finished building: fold it in at the
                # next epoch (the reconfigure ships it rounds + state)
                self.membership.mark_rejoined(wid)
                self._mark_event(wid, "worker_rejoined",
                                 f"enters at epoch {self.membership.epoch + 1}")
                self._reconfigure_p2p()
            elif kind == "reconf_ack":
                # a restarted reconfigure makes workers ack the same epoch
                # twice (once per phase 1 they saw); the collection loop
                # consumed one set, the leftovers are harmless latecomers
                continue
            else:
                raise RuntimeError(
                    f"protocol violation on the p2p control plane: "
                    f"got {kind} from worker {wid} ({detail!r})")

    def _admit_rejoin(self, wid: int, link: Link, peer) -> None:
        """Wire a respawned worker back in: register its link + reader and
        send a rejoin WELCOME. The worker builds its problem and warms up
        while the run keeps going; its READY triggers the reconfiguration
        that actually folds it into the mesh."""
        if not peer:
            link.send_json(wire.ERROR,
                           {"msg": "p2p rejoin needs a peer listener"})
            link.close()
            return
        self.peer_addrs[wid] = list(peer)
        self.links[wid] = link
        # the ORIGINAL spawned process for this wid is a corpse that stays
        # in self._procs; mark it reported forever so its exit code is
        # never mistaken for a death of the respawn (an external process
        # whose loss surfaces through its socket, not this poll)
        self._proc_reported.add(wid)
        self._mark_event(wid, "worker_rejoining")
        link.send_json(wire.WELCOME, self._welcome_payload(wid, rejoin=True))
        th = threading.Thread(target=self._reader, args=(wid, link),
                              daemon=True)
        th.start()
        self._threads.append(th)

    def _reconfigure_p2p(self) -> None:
        """Freeze → re-resolve → rewire → resume (the membership tentpole's
        master half).

        Phase 1 ships the next epoch's full geometry — survivor roster,
        rounds re-resolved for P′ and remapped onto the surviving wids, new
        padding and bucket boundaries, peer directory — to every member.
        Survivors stop at an exchange boundary (or fall out of the doomed
        exchange), tear their mesh links down, and ack with the number of
        exchange rounds they have fully completed. Phase 2 broadcasts the
        agreed resume round — the MINIMUM over acks, every worker ahead of
        it rolls back to its start-of-round snapshot so the new epoch's
        first exchange runs over bitwise-agreeing replicas — plus the new
        eval cadence; when a rejoiner is present, the lowest previous
        survivor uploads its rolled-back state and the master relays it so
        the rejoiner enters with the exact center (and velocity) bits.
        Another loss mid-reconfigure restarts the procedure with the
        smaller roster."""
        cfg = self.cfg
        while True:
            prev = sorted(w for w in self._epoch_members if w in self.links)
            roster = sorted(self.links)
            if not prev:
                raise RuntimeError(
                    "elastic: no previous-epoch survivor holds the state — "
                    "the run cannot continue")
            p = len(roster)
            epoch = self.membership.epoch + 1
            padded = self.n + (-self.n) % p
            rounds = comm_schedules.get(self.sched_name).rounds(
                p, self.n * 8, cfg.net)
            self.rounds = comm_rounds.remap_rounds(
                rounds, ft_membership.dense_rank_map(roster))
            self.padded = padded
            if getattr(cfg, "bucket_bytes", 0) > 0:
                self.boundaries = comm_rounds.default_bucket_boundaries(
                    self._layer_sizes, padded, cfg.bucket_bytes)
            joiners = [w for w in roster if w not in prev]
            sync_wid = prev[0]
            phase1 = {
                "phase": 1, "epoch": epoch, "p": p,
                "survivors": roster,
                "rounds": comm_schedules.rounds_to_wire(self.rounds),
                "padded": padded,
                "peers": {str(w): self.peer_addrs[w] for w in roster},
                "bucket_bounds": self.boundaries,
                "n_rounds": self._n_sync_rounds(),
                "sync_wid": sync_wid,
                "reporter": roster[0],
            }
            try:
                for w in roster:
                    slow = cfg.link_slow_factor(w)
                    self.links[w].send_json(wire.RECONFIGURE, {
                        **phase1,
                        "t_wire_s": slow * self._t_sync_wire(),
                        "t_wire_bucket_s": (
                            [slow * t for t in self._t_sync_wire_buckets()]
                            if self.boundaries else []),
                    }, wid=w)
            except (wire.WireError, OSError) as exc:
                # a member died under the broadcast: its reader will surface
                # the loss; drain it below and restart with the new roster
                self._mark_event(-1, "reconfigure_retry", repr(exc))
            # -- collect acks (and absorb whatever else is in flight) -------
            acks: dict[int, dict] = {}
            restart = False
            while set(acks) < set(roster):
                wid, kind, detail = self._next_event(self.timeout)
                if kind == "reconf_ack":
                    if int(detail.get("epoch", -1)) == epoch:
                        acks[wid] = detail
                elif kind == "member_lost":
                    restart = True
                    break
                elif kind == "center":
                    self._p2p_center_report(*detail)   # pre-freeze report
                elif kind == "wstate":
                    self.workers_w[wid] = self.wstate_bufs[wid]
                elif kind == "rejoin_hello":
                    # stash: admitted after this reconfigure completes (the
                    # serve loop re-enqueues it) — re-queuing here would
                    # spin this very collection loop
                    self._pending_rejoin.append((wid, detail))
                elif kind == "ready":
                    # an already-admitted rejoiner finished building while
                    # this reconfigure was in flight: it is in the roster,
                    # its ack follows
                    self.membership.mark_rejoined(wid)
                    self._mark_event(wid, "worker_rejoined",
                                     f"enters at epoch {epoch}")
                else:
                    raise RuntimeError(
                        f"protocol violation during reconfigure: "
                        f"got {kind} from worker {wid}")
            if restart:
                continue
            resume = min(int(acks[w]["round"]) for w in prev)
            # -- phase 2: agreed resume round + new eval cadence ------------
            per = p * self.tau
            last = self._last_eval
            base_iters = self._p2p_iters_at(resume - 1)
            evals = []
            for k in range(resume, self._n_sync_rounds()):
                it = base_iters + (k + 1 - resume) * per
                if it - last >= cfg.eval_every_iters:
                    evals.append(k)
                    last = it
            phase2 = {"phase": 2, "epoch": epoch, "resume_round": resume,
                      "eval_rounds": evals,
                      "upload_state": bool(joiners)}
            try:
                for w in roster:
                    self.links[w].send_json(wire.RECONFIGURE, phase2, wid=w)
                if joiners:
                    # the sync_wid uploads its rolled-back state; relay it
                    # to every joiner so they enter with the exact bits
                    state = None
                    while state is None:
                        wid, kind, detail = self._next_event(self.timeout)
                        if kind == "center" and detail[0] == -2:
                            state = detail[1]
                        elif kind == "center":
                            self._p2p_center_report(*detail)
                        elif kind == "member_lost":
                            restart = True
                            break
                        elif kind == "reconf_ack":
                            continue     # stale duplicate from a restart
                        elif kind == "wstate":
                            self.workers_w[wid] = self.wstate_bufs[wid]
                        elif kind == "rejoin_hello":
                            self._pending_rejoin.append((wid, detail))
                        else:
                            raise RuntimeError(
                                f"protocol violation waiting for the state "
                                f"upload: got {kind} from worker {wid}")
                    if restart:
                        continue
                    for w in joiners:
                        # raw: exact-state transfer, never through a lossy
                        # wire codec
                        self.links[w].send_array(wire.CENTER, state,
                                                 wid=-2, raw=True)
            except (wire.WireError, OSError) as exc:
                self._mark_event(-1, "reconfigure_retry", repr(exc))
                continue
            # -- bookkeeping: the epoch turns over --------------------------
            self._epoch_iters_base = base_iters
            self._epoch_round_base = resume
            self._epoch_p = p
            self._epoch_members = set(roster)
            new_epoch = self.membership.advance_epoch()
            assert new_epoch == epoch, (new_epoch, epoch)
            if self.live is not None:
                self.live.set_membership(roster)
            self.counters.gauge("epoch").value = epoch
            self._mark_event(
                -1, "reconfigure",
                f"epoch {epoch}: p={p} survivors={roster} "
                f"resume_round={resume}")
            for w, d in self._pending_rejoin:      # stashed mid-freeze
                self.events.put((w, "rejoin_hello", d))
            self._pending_rejoin.clear()
            return

    # -- top level -----------------------------------------------------------

    def run(self, listener: socket.socket, token: str = DEFAULT_TOKEN,
            procs: list | None = None):
        """Rendezvous → serve → clean shutdown. Returns a PSResult."""
        self._procs = procs or []
        try:
            self.rendezvous(listener, token)
            if self.cfg.telemetry_on:
                self._start_live(listener, token)
            if self.cfg.telemetry_on or self.elastic:
                # the rendezvous listener stays open: STATS for monitors,
                # rejoin HELLOs for respawned workers
                self._start_acceptor(listener, token)
            self.serve()
            total_time = time.perf_counter() - self._t0
            self._maybe_eval(force=True)
            self._draining = True        # BYEs are expected from here on
            for wid, link in list(self.links.items()):
                try:
                    link.send_simple(wire.DONE)
                except (wire.WireError, OSError):
                    if not self.elastic:
                        raise            # elastic: its loss drains below
            self._await("bye", set(self.links),
                        ignore=("grad", "wstate", "center"))
        finally:
            self._closing.set()
            for link in self.links.values():
                link.close()
            listener.close()
            for proc in self._procs:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        counters = self.counters.snapshot()
        # heartbeat-piggybacked worker telemetry (iteration rate, exposed
        # comm so far) — last value seen per worker; absent for workers
        # whose client predates the telemetry heartbeats (empty frames)
        telemetry = {w: link.hb_telemetry
                     for w, link in self.links.items() if link.hb_telemetry}
        if telemetry:
            counters["worker_telemetry"] = telemetry
        if self.cfg.wire_compression == "sign_ef":
            raw = sum(link.raw_bytes_out for link in self.links.values())
            comp = sum(link.wire_bytes_out for link in self.links.values())
            if comp:
                counters["ef_raw_bytes_out"] = raw
                counters["ef_wire_bytes_out"] = comp
                counters["ef_ratio"] = round(raw / comp, 2)
        # per-link α observations: each worker's measured master-link RTT
        # (the clock-sync probes double as the α measurement — rtt/2 is
        # this link's one-way latency floor)
        link_alpha = {w: round(st["clock"]["rtt_s"] / 2, 6)
                      for w, st in self.bye_stats.items()
                      if isinstance(st.get("clock"), dict)
                      and "rtt_s" in st["clock"]}
        if link_alpha:
            counters["link_alpha_s"] = link_alpha
        if self.sync_p2p:
            # fold the workers' per-link data-plane counters in: each
            # unordered link (i, j) once, from the LOWER endpoint's report
            # (both endpoints count every frame on the link — sends and
            # receives — so the two reports agree; tests pin that)
            link_bytes: dict[str, int] = {}
            msgs = 0
            for wid, st in sorted(self.bye_stats.items()):
                for peer, c in st.get("peer_links", {}).items():
                    if wid < int(peer):
                        link_bytes[f"{wid}-{peer}"] = c["wire_bytes"]
                        msgs += c["messages"]
            counters["peer_link_bytes"] = link_bytes
            counters["peer_wire_bytes"] = sum(link_bytes.values())
            counters["peer_messages"] = msgs
            if self.topology is not None and self.topology.hosts > 1:
                # per-link-class totals: how many bytes stayed on fast
                # intra-host links vs crossed hosts — hierarchical's whole
                # point is driving cross_host_bytes down
                intra_b = cross_b = 0
                for key, v in link_bytes.items():
                    i, j = (int(x) for x in key.split("-"))
                    if self.topology.host_of(i) == self.topology.host_of(j):
                        intra_b += int(v)
                    else:
                        cross_b += int(v)
                counters["intra_host_bytes"] = intra_b
                counters["cross_host_bytes"] = cross_b
            # representative per-worker stats come from the LOWEST reporting
            # wid — under elastic membership worker 0 may not have survived
            rep = (self.bye_stats[min(self.bye_stats)]
                   if self.bye_stats else {})
            counters["sync_rounds"] = rep.get("sync_rounds", 0)
            # overlap accounting: summed across workers (wall seconds of
            # comm-thread activity vs seconds the update path sat blocked
            # on the wire); per-bucket logical payload summed elementwise
            for key in ("comm_s", "exposed_s", "overlapped_s"):
                counters[key] = sum(
                    st.get(key, 0.0) for st in self.bye_stats.values())
            counters["n_buckets"] = rep.get("n_buckets", 1)
            bucket_bytes = [0] * counters["n_buckets"]
            for st in self.bye_stats.values():
                for i, v in enumerate(st.get("bucket_send_bytes", [])):
                    if i < len(bucket_bytes):  # epochs can differ in buckets
                        bucket_bytes[i] += int(v)
            counters["bucket_send_bytes"] = bucket_bytes
        health = None
        if self.live is not None:
            health = self.live.health()
            self.live.close()
        if self.elastic:
            # PSResult.health must name every death / rejoin / reconfigure
            # even on a bare (telemetry-off) run — and always carries the
            # final membership table + epoch
            if health is None:
                health = {"events": list(self._elastic_events)}
            health["membership"] = self.membership.snapshot()
            health["epoch"] = self.membership.epoch
        trace = self._collect_trace() if self.cfg.trace else None
        return PSResult(
            algorithm=self.cfg.algorithm, transport="tcp",
            schedule=((self.sched_name + "+p2p") if self.sync_p2p
                      else self.sched_name if self.cfg.algorithm in SYNC
                      else "master"),
            history=self.history, total_time_s=total_time,
            total_iters=self.iters,
            counters=counters,
            final_metric=self.history[-1][2],
            center=self.center.copy(), workers=self.workers_w.copy(),
            trace=trace, health=health)

    def _collect_trace(self):
        """Merge the workers' BYE-delivered (or spilled) trace buffers with
        this master's own tracers onto the master clock — each worker span
        is shifted by its ``obs.clock`` offset estimate."""
        workers: dict = {}
        for wid, st in self.bye_stats.items():
            payload = st.get("trace")
            if payload is None and st.get("trace_file"):
                try:
                    payload = obs_trace.load_spill(st["trace_file"])
                except OSError:
                    payload = None
            if payload:
                workers[wid] = payload
        master_threads = {t.name: t.spans() for t in obs_trace.drain()
                          if t.n}
        merged = obs_report.merge_traces(
            workers,
            {"threads": master_threads} if master_threads else None)
        merged["report"] = obs_report.breakdown(merged)
        return merged


def accept_backlog(n_workers: int) -> int:
    """Rendezvous listen() backlog: every worker dials within the same
    spawn burst, so at P = 64 a backlog of P + 2 overflows the SYN queue
    the moment the accept loop blocks on a slow HELLO and late dialers
    see connection-refused. Floor of 16 keeps small runs unchanged in
    behavior; + 8 leaves room for monitor/STATS dials on top of P."""
    return max(16, n_workers + 8)


def run_ps_tcp(problem, easgd, cfg, eval_fn_override=None,
               join_timeout_s: float = 600.0):
    """The tcp transport's ``run_ps``: bind, spawn localhost workers (unless
    ``cfg.spawn_workers`` is off — then external workers join, see
    launch/cluster), serve, return the same PSResult the shared-memory
    transports produce."""
    master = MasterServer(problem, easgd, cfg,
                          eval_fn_override=eval_fn_override,
                          join_timeout_s=join_timeout_s)
    listener = socket.socket()
    listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    listener.bind((cfg.tcp_host, cfg.tcp_port))
    listener.listen(accept_backlog(cfg.n_workers))
    port = listener.getsockname()[1]
    env_extra = None
    spec = ft_chaos.ChaosSpec.from_config(getattr(cfg, "chaos", None))
    if spec is not None:
        env_extra = {ft_chaos.ENV_VAR: spec.to_env()}
    procs = (spawn_local_workers(
        cfg.tcp_host, port, cfg.n_workers,
        pallas=getattr(cfg, "update_backend", "numpy") == "pallas",
        env_extra=env_extra)
        if cfg.spawn_workers else [])
    return master.run(listener, procs=procs)
