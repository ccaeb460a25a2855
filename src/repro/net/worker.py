"""The repro.net worker: a thin gradient client, runnable on any host.

    PYTHONPATH=src python -m repro.net.worker --connect HOST:PORT --wid 0

Deliberately minimal: numpy, the wire, and the problem factory named by the
master's WELCOME — no jax, no optimizer state beyond what τ>1 local steps
need. Under the MASTER sync plane all concurrency disciplines look
identical from here (the master decides when WEIGHTS arrive):

    HELLO → WELCOME (problem spec + algorithm + τ) → build + warmup → READY
    then per exchange:  recv WEIGHTS → [τ−1 local steps] → grad → send GRAD
    until DONE → BYE.

Under the P2P sync plane (``PSConfig.sync_plane="p2p"``, sync family only)
the worker IS the data plane: it opens a peer listener before HELLO and
advertises it, receives the peer directory + the registry's resolved
``Schedule.rounds`` in WELCOME, wires a ``net.peer.PeerMesh`` to every peer
its rounds talk to, and then trains WITHOUT per-round master traffic —
each exchange executes the rounds over direct worker↔worker SEGMENT
frames, every worker advancing its own bitwise-identical center replica
(see net/peer.py for why the rows agree). The master link carries only
control traffic plus worker 0's CENTER reports at eval rounds and one
final WSTATE per worker, so the Θ(P·N) master incast of the centralized
plane collapses to Θ(N_center).

A background thread heartbeats every ``hb_interval_s`` so the master can
tell a slow gradient from a dead host. With τ>1 the worker's local (w, v)
evolve between exchanges (``easgd_flat.local_step`` — the same rule the
shared-memory transports run), so frames stack [w|v] down and [grad|w|v]
up; sync_easgd instead posts its evolved weights (WSTATE) BEFORE computing
the exchange gradient, keeping the master's allreduce overlapped with
compute (paper §6.1.3) — in p2p mode the same overlap is preserved by
running the round executor in a background thread while the exchange
gradient is computed.
"""
from __future__ import annotations

import argparse
import importlib
import os
import socket
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np

from repro.core import easgd_flat
from repro.ft import chaos as ft_chaos
from repro.ft.watchdog import Watchdog
from repro.net import wire
from repro.net.peer import MeshAbort, PeerMesh
from repro.net.wire import Link, sleep_until
from repro.obs import clock as obs_clock
from repro.obs import trace as obs_trace

SYNC = easgd_flat.SYNC_FAMILY


def _connect(host: str, port: int, timeout_s: float = 30.0,
             seed: int | None = None, refuse_fn=None) -> socket.socket:
    """Dial the master with jittered exponential backoff and a hard
    deadline (``wire.dial_with_backoff``) — a worker that starts before
    the master's listener, or during a chaos dial-refuse window, absorbs
    the gap instead of crashing the launch."""
    return wire.dial_with_backoff(host, port, deadline_s=timeout_s,
                                  seed=seed, refuse_fn=refuse_fn)


def _build_problem(factory: str, kwargs):
    mod_name, fn_name = factory.split(":")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    return fn(**dict((k, v) for k, v in kwargs))


def _drain_after_bye(link: Link, timeout_s: float = 5.0) -> None:
    """After a mid-run BYE, read (and discard) until the master hangs up.
    Closing our end first with unread frames in the receive buffer would
    RST the connection and can destroy the master's still-unread BYE —
    the clean departure would then look like a dead socket."""
    try:
        link.sock.settimeout(timeout_s)
        while True:
            link.recv_discard(link.recv_header())
    except (OSError, wire.WireError):
        pass


def worker_loop(host: str, port: int, wid: int,
                token: str = "repro-net", timeout_s: float = 600.0,
                peer_host: str | None = None, peer_port: int = 0,
                sync_plane: str = "auto",
                heartbeat_file: str | None = None,
                rejoin: bool = False) -> None:
    # preemption plane: SIGTERM/SIGINT set a flag the train loops poll at
    # exchange boundaries — the worker then flushes its trace/telemetry in
    # a clean BYE instead of vanishing mid-frame. The optional heartbeat
    # file lets an external supervisor (launch/cluster --heartbeat-file)
    # tell a hung interpreter from a slow one.
    wd = Watchdog(heartbeat_path=heartbeat_file, interval_s=2.0)
    wd.start_heartbeat()
    # fault injection (ft.chaos): armed from REPRO_CHAOS, inert otherwise
    chaos = ft_chaos.clock_from_env()
    link = Link(_connect(host, port, timeout_s=min(timeout_s, 30.0),
                         seed=wid,
                         refuse_fn=lambda: chaos.refuse_dial(wid)))
    link.sock.settimeout(timeout_s)
    # the peer listener binds BEFORE HELLO so its port can ride in it
    # (sync_plane="master" skips it — no point advertising a dead port).
    # It binds to the interface the master link runs over — a loopback-only
    # run must not expose worker listeners on every interface — and
    # advertises that same address unless --peer-host overrides it.
    local_addr = link.sock.getsockname()[0]
    mesh = (PeerMesh(wid, token, bind_host=peer_host or local_addr,
                     port=peer_port, timeout_s=timeout_s)
            if sync_plane != "master" else None)
    hello = {"wid": wid, "token": token}
    if mesh is not None:
        hello["peer"] = [peer_host or local_addr, mesh.port]
    if rejoin:
        # respawned mid-run: the master's control acceptor (not the
        # rendezvous) answers this HELLO and folds us in at the next epoch
        hello["rejoin"] = True
    link.send_json(wire.HELLO, hello, wid=wid)
    frame = link.recv_header()
    if frame.ftype == wire.ERROR:
        raise RuntimeError(f"master rejected us: {link.recv_json(frame)}")
    assert frame.ftype == wire.WELCOME, frame
    cfg = link.recv_json(frame)
    link.codec = wire.CODECS[cfg.get("codec", "none")]
    algo, n, tau = cfg["algorithm"], int(cfg["n"]), int(cfg["tau"])
    local_cfg = SimpleNamespace(eta=cfg["eta"], mu=cfg["mu"],
                                rho=cfg.get("rho", 0.0),
                                alpha=cfg["eta"] * cfg.get("rho", 0.0))
    velocity = easgd_flat.uses_velocity(algo) and algo not in SYNC
    p2p = cfg.get("sync_plane") == "p2p"
    if p2p and mesh is None:
        raise RuntimeError(
            "master runs sync_plane=p2p but this worker was started with "
            "--sync-plane master (no peer listener to join the mesh with)")
    if not p2p and mesh is not None:
        mesh.close()                             # advertised, never needed
        mesh = None

    # tracing rides in WELCOME; the clock handshake runs NOW, while the
    # link is otherwise quiet (CLOCK replies are the only inbound frames
    # between WELCOME and the first WEIGHTS), so rtt is measured clean
    tracing = bool(cfg.get("trace"))
    trace_dir = cfg.get("trace_dir") or None
    tr = obs_trace.tracer("main", wid=wid) if tracing else None
    clk = obs_clock.sync_over_link(link, wid=wid) if tracing else None
    telem = {"iters": 0, "rate_ips": 0.0, "exposed_s": 0.0}
    t_start = time.perf_counter()

    stop_hb = threading.Event()

    def _heartbeat():
        interval = float(cfg.get("hb_interval_s", 2.0))
        while not stop_hb.wait(interval):
            try:
                # liveness + telemetry in one frame: current iteration
                # count, smoothed rate, and exposed comm so far — the
                # master keeps the last sample per worker
                el = max(time.perf_counter() - t_start, 1e-9)
                link.send_json(wire.HEARTBEAT, {
                    "iters": telem["iters"],
                    "rate_ips": round(telem["iters"] / el, 2),
                    "exposed_s": round(telem["exposed_s"], 4),
                }, wid=wid)
            except OSError:
                return

    def _trace_payload():
        threads = {"main": tr.spans()}
        for t in obs_trace.drain():
            if t is not tr and t.wid == wid:
                threads[t.name] = t.spans()
        return {"clock": clk.to_wire(), "threads": threads,
                "dropped": tr.dropped}

    def _bye_stats(stats: dict) -> dict:
        if not tracing:
            return stats
        payload = _trace_payload()
        if trace_dir:
            stats["trace_file"] = obs_trace.dump_spill(
                trace_dir, wid, payload)
        else:
            stats["trace"] = payload
        stats["clock"] = clk.to_wire()
        return stats

    # heartbeat from BEFORE the problem build: a slow build (jax import +
    # jit in a fresh interpreter) must read as alive, not silent
    hb = threading.Thread(target=_heartbeat, daemon=True)
    hb.start()

    w0, grad_fn, _ = _build_problem(cfg["factory"], cfg["kwargs"])
    w = np.zeros(n)
    v = np.zeros(n) if velocity else None
    down = np.zeros(2 * n) if (velocity and tau > 1) else w
    for k in range(int(cfg.get("warmup", 2))):   # private RNG streams ≤ −2:
        grad_fn(w, k, -(wid + 2))                # worker streams untouched
    try:
        if p2p:
            _p2p_sync_loop(link, mesh, cfg, grad_fn,
                           np.asarray(w0, np.float64), wid, local_cfg,
                           tr=tr, telem=telem, bye_wrap=_bye_stats,
                           watchdog=wd, chaos=chaos)
            return
    except BaseException as exc:                 # noqa: BLE001 — tell master
        try:
            link.send_json(wire.ERROR, {"msg": repr(exc)}, wid=wid)
        except OSError:
            pass
        raise
    finally:
        if p2p:
            stop_hb.set()
            wd.close()
            if mesh is not None:
                mesh.close()
            link.close()
    link.send_simple(wire.READY, wid=wid)

    step = 0
    _pc = time.perf_counter
    try:
        while True:
            if wd.should_stop.is_set():
                # preempted: flush traces/telemetry and leave cleanly —
                # the master surfaces this as a named worker_left event
                link.send_json(wire.BYE, _bye_stats(
                    {"preempted": True, "iters": telem["iters"]}), wid=wid)
                _drain_after_bye(link)
                return
            chaos.maybe_fire(wid, step)          # deterministic fault point
            if tr is not None:
                t0 = _pc()
            frame = link.recv_header()
            if frame.ftype == wire.DONE:
                link.recv_discard(frame)
                if tracing:
                    link.send_json(wire.BYE, _bye_stats({}), wid=wid)
                else:
                    link.send_simple(wire.BYE, wid=wid)
                return
            if frame.ftype == wire.ERROR:
                raise RuntimeError(
                    f"master error: {link.recv_json(frame)}")
            assert frame.ftype == wire.WEIGHTS, frame
            link.recv_array(frame, down)
            if tr is not None:
                # blocked on the master's WEIGHTS: exposed communication
                t1 = _pc()
                tr.record(obs_trace.RECV_WAIT, t0, t1)
                telem["exposed_s"] += t1 - t0
                t0 = t1
            if down is not w:
                w[:] = down[:n]
                v[:] = down[n:]
            for _ in range(tau - 1):             # τ−1 local-only steps
                grad = grad_fn(w, step, wid)
                easgd_flat.local_step(algo, w, v if velocity else w,
                                      grad, local_cfg)
                step += 1
            if tr is not None and tau > 1:
                tr.record(obs_trace.LOCAL_STEP, t0, (t0 := _pc()), tau - 1)
            if algo == "sync_easgd" and tau > 1:
                # post evolved weights FIRST: the master's allreduce
                # overlaps the gradient we are about to compute
                link.send_array(wire.WSTATE, w, wid=wid)
            grad = grad_fn(w, step, wid)
            step += 1
            if tr is not None:
                tr.record(obs_trace.COMPUTE, t0, _pc())
            telem["iters"] = step
            if tau > 1 and algo not in SYNC:
                # stacked upload: one frame, but each segment keeps its own
                # sign-EF scale/state (grad and weight magnitudes must not
                # share a quantization scale)
                up = (np.concatenate([grad, w, v]) if velocity
                      else np.concatenate([grad, w]))
                link.send_array(wire.GRAD, up, wid=wid,
                                segments=3 if velocity else 2)
            else:
                link.send_array(wire.GRAD, grad, wid=wid)
    except BaseException as exc:                 # noqa: BLE001 — tell master
        try:
            link.send_json(wire.ERROR, {"msg": repr(exc)}, wid=wid)
        except OSError:
            pass
        raise
    finally:
        stop_hb.set()
        wd.close()
        link.close()


def _p2p_sync_loop(link: Link, mesh: PeerMesh, cfg: dict, grad_fn,
                   w0: np.ndarray, wid: int, local_cfg,
                   tr=None, telem=None, bye_wrap=None,
                   watchdog=None, chaos=None) -> None:
    """The p2p sync family: this worker executes its share of the
    registry's rounds over the peer mesh and advances its OWN center
    replica — bitwise in lockstep with every other worker and with the
    centralized planes (same ops on bitwise-equal rows, see net/peer.py).
    The master link goes quiet between READY and DONE except for worker
    0's CENTER reports at the eval rounds shipped in WELCOME.

    With ``bucket_bounds`` in WELCOME the exchange streams the row as
    per-layer-group buckets and PIPELINES comm with compute: the mesh's
    ``on_bucket`` hook hands completed buckets to this thread, which
    applies bucket b's elastic update while bucket b+1 is still on the
    wire. Bucket updates are elementwise on disjoint slices in schedule
    order, so the iterates stay bitwise-identical to the monolithic path
    — overlap moves time, never math. ``overlap=False`` runs the same
    bucketed exchange inline first (the paper's no-overlap baseline).

    Under ``elastic`` (WELCOME flag, from ``PSConfig.elastic``) this loop
    is also the worker half of the membership tentpole (ft.membership): a
    control-reader thread owns the master link's inbound side and routes
    RECONFIGURE/CENTER/DONE/ERROR into a queue; a peer death surfaces as a
    failed exchange (``mesh.reset()`` cascades so every survivor falls out
    fast), a pure join as a flag checked at the round boundary, and both
    enter ``_recover`` — ack the freeze with the rounds completed, roll
    back to the 2-deep start-of-round snapshot the master's agreed
    ``resume_round`` names, rewire the mesh to the new epoch's geometry,
    and continue in the same process. With ``elastic`` off none of this
    machinery exists at runtime (no thread, no snapshots): the happy path
    stays bitwise AND cost-identical to the pre-membership loop."""
    import queue as _queue

    from repro.comm.rounds import peer_pairs, rounds_from_wire

    algo, n, tau = cfg["algorithm"], int(cfg["n"]), int(cfg["tau"])
    P, padded = int(cfg["p"]), int(cfg["padded"])
    n_rounds = int(cfg["n_rounds"])
    eval_rounds = set(int(k) for k in cfg["eval_rounds"])
    t_wire = float(cfg.get("t_wire_s", 0.0))
    bounds = cfg.get("bucket_bounds") or None
    overlap = bool(cfg.get("overlap", True))
    t_bucket = [float(x) for x in (cfg.get("t_wire_bucket_s") or [])]
    rounds = rounds_from_wire(cfg["rounds"])
    directory = {int(k): v for k, v in cfg["peers"].items()}
    elastic = bool(cfg.get("elastic"))
    rejoin = bool(cfg.get("rejoin"))
    reporter = 0                   # lowest live wid sends CENTER reports
    mesh.codec = cfg.get("codec", "none")
    topo_wire = cfg.get("topology")
    if topo_wire and int(topo_wire.get("hosts", 1)) > 1:
        # two-level fabric: label this worker's peer links intra/cross in
        # the BYE stats (pacing itself needs nothing here — the master
        # ships per-wid t_wire_s already priced for OUR links)
        slots = int(topo_wire["slots"])
        mesh.host_of = lambda w: -1 if w < 0 else w // slots
    if not rejoin:
        # a rejoiner holds off: the RECONFIGURE that folds it in names the
        # epoch's actual geometry (the WELCOME's copy is already stale the
        # moment the next membership event lands)
        mesh.connect(directory, peer_pairs(rounds))
        mesh.set_rounds(rounds, padded, boundaries=bounds)

    # -- elastic control plane: ONE thread owns the master link's inbound
    # side for the whole run (RECONFIGURE can land at any moment, so the
    # main thread can never block on a direct recv) and routes frames into
    # a queue the train loop and the recovery path consume from
    ctrl_q: _queue.SimpleQueue = _queue.SimpleQueue()
    pending_reconf = [0]           # phase-1s seen, not yet consumed (GIL-
    ctrl_th = None                 # atomic int updates, no lock needed)

    def _ctrl_reader():
        try:
            while True:
                frame = link.recv_header()
                if frame.ftype == wire.RECONFIGURE:
                    payload = link.recv_json(frame)
                    if payload.get("phase") == 1:
                        pending_reconf[0] += 1
                    ctrl_q.put(("reconf", payload))
                elif frame.ftype == wire.CENTER:
                    ctrl_q.put(("center", link.recv_array(frame)))
                elif frame.ftype == wire.DONE:
                    link.recv_discard(frame)
                    ctrl_q.put(("done", None))
                    return
                elif frame.ftype == wire.ERROR:
                    ctrl_q.put(("error", link.recv_json(frame)))
                    return
                else:
                    link.recv_discard(frame)
        except (wire.WireError, OSError):
            ctrl_q.put(("dead", None))

    def _ctrl_get():
        kind, payload = ctrl_q.get()
        if kind == "error":
            raise RuntimeError(f"master error: {payload}")
        if kind == "dead":
            raise wire.WireError("master link died mid-run")
        return kind, payload

    if elastic:
        # started BEFORE READY: a rejoiner's READY makes the master fire
        # the folding RECONFIGURE immediately
        ctrl_th = threading.Thread(target=_ctrl_reader, daemon=True)
        ctrl_th.start()
    link.send_simple(wire.READY, wid=wid)        # mesh up, clock may start

    w = w0.copy()                  # same bits as the master's problem build
    center = w0.copy()             # the center replica (all workers agree)
    vel = np.zeros(n)              # sync_sgd's master velocity replica
    row = np.zeros(padded)         # this worker's mailbox row
    exc_box: list = []
    done_q: _queue.SimpleQueue = _queue.SimpleQueue()
    if rejoin:
        n_buckets, u_spans, pace = 0, [], None   # set by the folding epoch
    else:
        n_buckets = mesh.n_buckets
        # update slices: bucket spans clamped to the real row (past n: pad)
        u_spans = [(a, min(b, n)) for a, b in zip(mesh.boundaries[:-1],
                                                  mesh.boundaries[1:])]
        pace = t_bucket if len(t_bucket) == n_buckets else None
    comm_s = exposed_s = 0.0                     # overlap accounting
    _pc = time.perf_counter
    tr_comm = obs_trace.tracer("comm", wid=wid) if tr is not None else None
    mesh.tracer = tr_comm                        # per-bucket wire spans

    def _on_bucket(bidx, deadlines):
        if deadlines is not None:                # serialized-wire pacing:
            sleep_until(deadlines[bidx])         # bucket lands on schedule
        done_q.put(bidx)

    def _exchange():
        nonlocal comm_s
        t0 = _pc()
        try:
            start = time.monotonic()
            deadlines = ([start + sum(t_bucket[:i + 1])
                          for i in range(n_buckets)] if pace else None)
            mesh.execute_exchange(
                row, on_bucket=lambda b: _on_bucket(b, deadlines))
            if t_wire and deadlines is None:
                sleep_until(start + t_wire)
        except BaseException as e:               # noqa: BLE001 — re-raised
            exc_box.append(e)
            done_q.put(None)                     # unblock the update loop
        finally:
            t1 = _pc()
            comm_s += t1 - t0
            if tr_comm is not None:
                tr_comm.record(obs_trace.EXCHANGE, t0, t1)

    def _apply_easgd(bidx, grad):
        a, b = u_spans[bidx]
        if a >= b:
            return
        easgd_flat.worker_step(algo, w[a:b], vel[a:b], grad[a:b],
                               center[a:b], local_cfg)
        easgd_flat.sync_master_easgd(center[a:b], row[a:b] / P, P,
                                     local_cfg)

    def _apply_sgd(bidx):
        a, b = u_spans[bidx]
        if a >= b:
            return
        easgd_flat.sync_master_sgd(center[a:b], vel[a:b],
                                   row[a:b] / P, local_cfg)

    def _drain(apply_fn):
        """Apply each bucket's update as it lands; time blocked on the
        wire is the EXPOSED communication this pipeline exists to hide."""
        nonlocal exposed_s
        for _ in range(n_buckets):
            t0 = time.perf_counter()
            bidx = done_q.get()
            t1 = time.perf_counter()
            exposed_s += t1 - t0
            if bidx is None:
                break
            if tr is not None:
                tr.record(obs_trace.BUCKET_WAIT, t0, t1, bidx)
            apply_fn(bidx)
            if tr is not None:
                tr.record(obs_trace.UPDATE, t1, time.perf_counter(), bidx)

    def _join_comm(comm):
        """Wait out the comm thread's tail — exposed by definition."""
        nonlocal exposed_s
        t0 = time.perf_counter()
        comm.join()
        t1 = time.perf_counter()
        exposed_s += t1 - t0
        if tr is not None:
            tr.record(obs_trace.COMM_WAIT, t0, t1)

    def _exchange_inline():
        """No-overlap baseline: the whole wire is exposed."""
        nonlocal exposed_s
        t0 = time.perf_counter()
        _exchange()
        t1 = time.perf_counter()
        exposed_s += t1 - t0
        if tr is not None:
            tr.record(obs_trace.COMM_WAIT, t0, t1)

    def _grad_traced(step):
        t0 = time.perf_counter()
        g = grad_fn(w, step, wid)
        if tr is not None:
            tr.record(obs_trace.COMPUTE, t0, time.perf_counter())
        return g

    # start-of-round snapshot ring, kept 2 deep (elastic only): the agreed
    # resume round is the MIN over survivor acks, and the allreduce mesh
    # bounds the completed-round spread to 1 (a worker finishes exchange k
    # only once every peer has entered it), so rolling back ever needs at
    # most the previous boundary
    snaps: dict = {}
    cur_epoch = 0

    def _recover(rounds_done, step_now, failed, first_p1=None,
                 joiner=False):
        """Worker half of the two-phase reconfigure (see server.py's
        ``_reconfigure_p2p``): tear the mesh down, ack phase 1 with the
        rounds fully completed, adopt phase 2's resume round (rolling back
        to its snapshot — or, for a joiner, the state the master relays),
        rewire to the new epoch's geometry, and return (resume, step)."""
        nonlocal P, padded, n_rounds, rounds, row, u_spans, n_buckets, \
            pace, t_wire, t_bucket, eval_rounds, reporter, cur_epoch
        p1 = first_p1
        while True:
            mesh.reset()                 # closes peer links: every survivor
            exc_box.clear()              # still blocked in the doomed
            while True:                  # exchange falls out right away
                try:
                    done_q.get_nowait()
                except _queue.Empty:
                    break
            while p1 is None:
                kind, payload = _ctrl_get()
                if kind == "done":
                    raise RuntimeError("master finished mid-reconfigure")
                if kind == "reconf" and payload.get("phase") == 1:
                    pending_reconf[0] -= 1
                    p1 = payload
            p2 = None
            while p2 is None:
                link.send_json(wire.RECONFIGURE,
                               {"epoch": int(p1["epoch"]),
                                "round": rounds_done,
                                "step": step_now}, wid=wid)
                while True:
                    kind, payload = _ctrl_get()
                    if kind == "done":
                        raise RuntimeError(
                            "master finished mid-reconfigure")
                    if kind != "reconf":
                        continue
                    if payload.get("phase") == 1:
                        # another loss mid-handshake: the master restarted
                        # with a smaller roster — re-ack the fresh epoch
                        pending_reconf[0] -= 1
                        p1 = payload
                        break
                    if int(payload.get("epoch", -1)) == int(p1["epoch"]):
                        p2 = payload
                        break
            resume = int(p2["resume_round"])
            if pending_reconf[0] > 0:
                # a fresh phase 1 is already queued (loss after phase 2
                # went out) — don't wire a doomed mesh, restart instead
                p1 = None
                continue
            # -- state: roll back, upload, or adopt -------------------------
            if joiner:
                arr = None
                while arr is None:
                    kind, payload = _ctrl_get()
                    if kind == "center":     # the relayed sync_wid state
                        arr = payload
                    elif kind == "done":
                        raise RuntimeError(
                            "master finished mid-reconfigure")
                center[:] = arr[:n]
                vel[:] = arr[n:2 * n] if arr.size >= 2 * n else 0.0
                w[:] = center
                step_now = resume * tau  # the survivors' step at resume
            else:
                if failed or resume != rounds_done:
                    try:
                        sw, sv, sc, sstep = snaps[resume]
                    except KeyError:
                        raise RuntimeError(
                            f"elastic: no snapshot for resume round "
                            f"{resume} (have {sorted(snaps)})") from None
                    w[:], vel[:], center[:] = sw, sv, sc
                    step_now = sstep
                if p2.get("upload_state") and wid == int(p1["sync_wid"]):
                    # lowest previous survivor: ship the rolled-back state
                    # so joiners enter with the exact center (and vel) bits
                    state = (center if algo == "sync_easgd"
                             else np.concatenate([center, vel]))
                    link.send_array(wire.CENTER, state, wid=-2, raw=True)
            # -- adopt the new epoch's geometry -----------------------------
            cur_epoch = int(p1["epoch"])
            P, padded = int(p1["p"]), int(p1["padded"])
            n_rounds = int(p1["n_rounds"])
            rounds = rounds_from_wire(p1["rounds"])
            t_wire = float(p1.get("t_wire_s", 0.0))
            t_bucket = [float(x) for x in (p1.get("t_wire_bucket_s") or [])]
            eval_rounds = set(int(x) for x in p2["eval_rounds"])
            reporter = int(p1["reporter"])
            row = np.zeros(padded)
            if resume < n_rounds:        # exchanges remain: rewire
                new_dir = {int(x): a for x, a in p1["peers"].items()}
                mesh.connect(new_dir, peer_pairs(rounds))
                mesh.set_rounds(rounds, padded,
                                boundaries=p1.get("bucket_bounds") or None)
                n_buckets = mesh.n_buckets
                u_spans = [(a, min(b, n))
                           for a, b in zip(mesh.boundaries[:-1],
                                           mesh.boundaries[1:])]
                pace = t_bucket if len(t_bucket) == n_buckets else None
            snaps.clear()                # pre-epoch snapshots are stale
            return resume, step_now

    step = 0
    k = 0
    if rejoin:
        # a respawn enters through recovery: ack round −1 (it is not a
        # previous-epoch survivor, so its ack never constrains the resume
        # round), adopt the relayed state, and start at the resume round
        k, step = _recover(-1, 0, failed=False, joiner=True)
    reported_final = False
    while True:
        while k < n_rounds:
            if elastic:
                snaps[k] = (w.copy(), vel.copy(), center.copy(), step)
                snaps.pop(k - 2, None)
                if pending_reconf[0] > 0:        # a join (no death) folds
                    k, step = _recover(k, step, failed=False)  # in here,
                    continue                     # at the round boundary
            if watchdog is not None and watchdog.should_stop.is_set():
                # preempted between rounds: the mesh is only safe to leave
                # at a round boundary (peers block on our segments
                # mid-exchange)
                stats = {"preempted": True, "iters": step}
                if bye_wrap is not None:
                    stats = bye_wrap(stats)
                link.send_json(wire.BYE, stats, wid=wid)
                if ctrl_th is not None:
                    ctrl_th.join(timeout=5.0)    # ends when master hangs up
                else:
                    _drain_after_bye(link)
                return
            if chaos is not None:
                chaos.maybe_fire(wid, step)      # deterministic fault point
            try:
                if tau > 1:
                    t0 = time.perf_counter()
                    for _ in range(tau - 1):     # τ−1 local-only steps
                        g = grad_fn(w, step, wid)
                        easgd_flat.local_step(algo, w, vel, g, local_cfg)
                        step += 1
                    if tr is not None:
                        tr.record(obs_trace.LOCAL_STEP, t0,
                                  time.perf_counter(), tau - 1)
                if algo == "sync_easgd":
                    row[:n] = w                  # start-of-exchange weights
                    if overlap:
                        comm = threading.Thread(target=_exchange)
                        comm.start()             # buckets fly while the
                        grad = _grad_traced(step)    # gradient computes
                        step += 1                # (paper §6.1.3)
                        _drain(lambda b: _apply_easgd(b, grad))
                        _join_comm(comm)
                    else:
                        _exchange_inline()
                        grad = _grad_traced(step)
                        step += 1
                        _drain(lambda b: _apply_easgd(b, grad))
                    if exc_box:
                        raise exc_box[0]
                else:                            # sync_sgd: grads first, so
                    grad = _grad_traced(step)    # only the per-bucket
                    step += 1                    # master update overlaps
                    row[:n] = grad               # (§5.1)
                    if overlap:
                        comm = threading.Thread(target=_exchange)
                        comm.start()
                        _drain(_apply_sgd)
                        _join_comm(comm)
                    else:
                        _exchange_inline()
                        _drain(_apply_sgd)
                    if exc_box:
                        raise exc_box[0]
                    w[:] = center
                if telem is not None:
                    telem["iters"] = step
                    telem["exposed_s"] = exposed_s
                    telem["comm_s"] = comm_s
                if wid == reporter and k in eval_rounds:
                    # control-plane reports go RAW even under wire
                    # compression (one-shot exact-state transfers, not a
                    # stream error feedback could correct over time) and
                    # TAGGED with the exchange round: reports and
                    # reconfigurations interleave, so the master can't
                    # infer the cadence from arrival order
                    link.send_array(wire.CENTER, center, wid=k, raw=True)
            except (wire.WireError, OSError, MeshAbort):
                if not elastic:
                    raise
                # a peer died: the exchange collapsed under us (mesh.reset
                # on any survivor cascades the collapse) — freeze, ack the
                # rounds completed, resume in the reconfigured epoch
                k, step = _recover(k, step, failed=True)
                continue
            k += 1
        # -- final reports: tagged center (−1) + this worker's weights ------
        if wid == reporter and not reported_final:
            link.send_array(wire.CENTER, center, wid=-1,    # Θ(N), not
                            raw=True)                       # Θ(P·N)
            reported_final = True
        link.send_array(wire.WSTATE, w, wid=wid, raw=True)  # final weights
        stats = mesh.stats()
        stats.update({"comm_s": comm_s, "exposed_s": exposed_s,
                      "overlapped_s": max(0.0, comm_s - exposed_s),
                      "overlap": overlap})
        if mesh.host_of is not None:
            stats["host"] = mesh.host_of(wid)
        if elastic:
            stats["epoch"] = cur_epoch
        if bye_wrap is not None:
            stats = bye_wrap(stats)
        if not elastic:
            while True:                          # control plane: DONE → BYE
                frame = link.recv_header()
                if frame.ftype == wire.DONE:
                    link.recv_discard(frame)
                    link.send_json(wire.BYE, stats, wid=wid)
                    return
                if frame.ftype == wire.ERROR:
                    raise RuntimeError(
                        f"master error: {link.recv_json(frame)}")
                link.recv_discard(frame)
        recovered = False
        while not recovered:                     # elastic: DONE → BYE, via
            kind, payload = _ctrl_get()          # the control thread
            if kind == "done":
                link.send_json(wire.BYE, stats, wid=wid)
                return
            if kind == "reconf" and payload.get("phase") == 1:
                # a member died during the final drain, before the last
                # CENTER landed: every exchange already completed
                # everywhere (resume == n_rounds), but the reporter may
                # have changed — recover, loop back, and re-report (the
                # master folds duplicate reports idempotently)
                pending_reconf[0] -= 1
                k, step = _recover(k, step, failed=False, first_p1=payload)
                reported_final = False
                recovered = True


def burn_main(spec_json: str, samples: int, wid: int) -> None:
    """Calibration burner: the EXACT worker substrate (same interpreter,
    same jax-free import footprint), measuring its own per-gradient wall
    period while its siblings run. Protocol: build+warm, print "R", wait
    for a line on stdin (the gate), burn, print the per-grad seconds.
    ``ps.calibrate`` uses the median across burners as the tcp transport's
    concurrent compute rate."""
    import json
    spec = json.loads(spec_json)
    w0, grad_fn, _ = _build_problem(spec["factory"], spec["kwargs"])
    w = np.asarray(w0, np.float64).copy()
    for k in range(5):
        grad_fn(w, k, -(wid + 2))
    print("R", flush=True)
    sys.stdin.readline()
    t0 = time.perf_counter()
    for k in range(samples):
        grad_fn(w, k, -(wid + 2))
    print((time.perf_counter() - t0) / samples, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--connect", default=None, metavar="HOST:PORT")
    ap.add_argument("--wid", type=int, default=-1,
                    help="worker id (default: from REPRO_CLUSTER_SPEC)")
    ap.add_argument("--token", default="repro-net")
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--sync-plane", default="auto",
                    choices=["auto", "master", "p2p"],
                    help="auto/p2p: open a peer listener and advertise it "
                         "in HELLO (the master's WELCOME decides whether "
                         "the p2p data plane is used); master: skip it")
    ap.add_argument("--peer-port", type=int, default=0,
                    help="fixed bind port for the peer listener (multi-host "
                         "p2p behind firewalls; 0 = ephemeral)")
    ap.add_argument("--peer-host", default=None,
                    help="address to advertise for the peer listener "
                         "(default: the local endpoint of the master link)")
    ap.add_argument("--heartbeat-file", default=None,
                    help="touch this file every ~2 s so an external "
                         "supervisor can detect a hung worker "
                         "(ft.Watchdog.is_alive)")
    ap.add_argument("--burn", default=None, metavar="SPEC_JSON",
                    help="calibration mode: measure this interpreter's "
                         "concurrent gradient rate instead of training")
    ap.add_argument("--samples", type=int, default=20)
    ap.add_argument("--rejoin", action="store_true",
                    help="rejoin a running elastic master mid-run (a "
                         "respawn is a re-exec with REPRO_CLUSTER_SPEC "
                         "set plus this flag)")
    args = ap.parse_args(argv)
    if args.burn is not None:
        burn_main(args.burn, args.samples, args.wid)
        return
    # the declarative spec (server.cluster_spec_env) fills any connection
    # detail the command line leaves out — a respawn needs no hand-crafted
    # flags beyond --rejoin
    spec = os.environ.get("REPRO_CLUSTER_SPEC")
    if spec:
        import json as _json
        spec = _json.loads(spec)
        if args.connect is None:
            args.connect = f"{spec['host']}:{spec['port']}"
        if args.wid < 0:
            args.wid = int(spec["wid"])
        if args.token == "repro-net" and "token" in spec:
            args.token = spec["token"]
        if args.sync_plane == "auto" and "sync_plane" in spec:
            args.sync_plane = spec["sync_plane"]
        if args.peer_port == 0 and "peer_port" in spec:
            args.peer_port = int(spec["peer_port"])
    if args.connect is None:
        ap.error("--connect is required (unless --burn or "
                 "REPRO_CLUSTER_SPEC is set)")
    if args.wid < 0:
        ap.error("--wid is required (unless REPRO_CLUSTER_SPEC names it)")
    host, port = args.connect.rsplit(":", 1)
    worker_loop(host, int(port), args.wid, token=args.token,
                timeout_s=args.timeout, peer_host=args.peer_host,
                peer_port=args.peer_port, sync_plane=args.sync_plane,
                heartbeat_file=args.heartbeat_file, rejoin=args.rejoin)


if __name__ == "__main__":
    main()
