"""Training driver: data pipeline → jitted Sync-EASGD step → checkpoints,
with preemption watchdog and elastic-restart support.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b \
        --reduced --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/run1

``--mode ps`` instead runs the repro.ps parameter-server runtime: any of
the paper's nine algorithms (or ``--algorithm all``) executed for real on
the thread or multiprocessing transport, with measured vs DES-predicted
per-iteration time printed side by side:

    PYTHONPATH=src python -m repro.launch.train --mode ps \
        --algorithm hogwild_easgd --transport thread --ps-workers 4

``--reduced`` trains the architecture's small CPU-test config; without it
the published widths are used. The sync mode spreads over every visible
device: ``--n-pods`` EASGD workers, each with ``devices / n_pods`` of them.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import time
from typing import Any

import jax
import numpy as np

from repro import comm, configs
from repro.checkpoint import CheckpointManager
from repro.core.easgd import EASGDConfig
from repro.core.elastic import ElasticConfig
from repro.data import ShardedPipeline, SyntheticLMStream
from repro.ft import Watchdog
from repro.launch.mesh import make_host_mesh
from repro.models.common import ModelConfig
from repro.runtime import sharding as shd
from repro.runtime.train import build_train_step

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing else is set. Otherwise the cache lives at ``<checkout>/
    .jax_cache``: a fixed path, since the path is part of the cache key."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def count_compiles(fun_name: str):
    """Yields ``[n, seconds]``: the backend compiles (or persistent-cache
    loads) of the jitted function ``fun_name`` while the block runs."""
    seen = [0, 0.0]
    tag = f"jit({fun_name})"

    def on_event(event, secs, **kw):
        if event == _COMPILE_EVENT and kw.get("fun_name") == tag:
            seen[0] += 1
            seen[1] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@dataclasses.dataclass
class SyncRun:
    """What one ``run_sync`` call did."""
    losses: list              # per step, in order
    step_seconds: list        # wall time per step, to the step's outputs
    step_compiles: int        # compiles (or cache loads) of the step
    compile_seconds: float    # their backend time
    state: Any                # final ElasticState, on the mesh
    mesh: Any
    build: Any                # the runtime.train.TrainBuild


def run_sync(cfg: ModelConfig, ecfg: ElasticConfig, *, steps: int,
             batch: int, seq: int, n_pods: int = 1, devices=None,
             microbatches: int = 1, ckpt_dir: str | None = None,
             ckpt_every: int = 25, log_every: int = 5) -> SyncRun:
    """The device Sync-EASGD path: mesh → ``build_train_step`` → data
    pipeline → step loop (with checkpoint resume and preemption stop).

    ``devices`` (default: all) are split into ``n_pods`` EASGD workers of
    ``len(devices) // n_pods`` data-parallel devices each; ``batch`` is the
    global batch in sequences."""
    devices = list(jax.devices() if devices is None else devices)
    n_dev = len(devices)
    if n_pods > 1:
        mesh = make_host_mesh((n_pods, max(1, n_dev // n_pods), 1),
                              ("pod", "data", "model"), devices=devices)
    else:
        mesh = make_host_mesh((n_dev, 1), ("data", "model"), devices=devices)
    per_pod = batch // n_pods
    build = build_train_step(cfg, ecfg, mesh, n_pods=n_pods,
                             per_pod_batch=per_pod, seq=seq,
                             microbatches=microbatches)
    batch_shardings = shd.named(mesh, build.batch_spec_tree)
    state = build.init_state()

    pipe = ShardedPipeline(
        lambda shard, n: SyntheticLMStream(cfg.vocab_size, seq, per_pod,
                                           seed=13, shard=shard, n_shards=n),
        n_pods=n_pods).start()

    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start_step = 0
    if ckpt and ckpt.latest_step() is not None:
        state, meta = ckpt.restore(state)
        state = jax.device_put(state, shd.named(mesh, build.state_specs))
        start_step = meta["extra"]["data_step"]
        pipe.restore(start_step)
        print(f"resumed from step {start_step}")

    wd = Watchdog().start_heartbeat()
    t0 = time.time()
    losses, step_s = [], []
    step = start_step
    try:
        with count_compiles(build.step.__name__) as compiles:
            for step in range(start_step, steps):
                if wd.should_stop.is_set():
                    print("preemption signal — checkpoint + clean exit")
                    break
                t_step = time.perf_counter()
                with jax.profiler.StepTraceAnnotation("train", step_num=step):
                    batch_dev = jax.device_put(pipe.next(), batch_shardings)
                    state, metrics = build.step(state, batch_dev)
                    jax.block_until_ready((state, metrics))
                step_s.append(time.perf_counter() - t_step)
                losses.append(float(metrics["loss"]))
                if step % log_every == 0:
                    print(f"step {step:5d} loss {losses[-1]:.4f} "
                          f"acc {float(metrics['accuracy']):.3f} "
                          f"({time.time()-t0:.1f}s)", flush=True)
                if ckpt and step and step % ckpt_every == 0:
                    ckpt.save_async(step, state,
                                    extra={"data_step": step + 1})
    finally:
        pipe.stop()
        if ckpt:
            ckpt.wait()
            ckpt.save(step, state, extra={"data_step": step + 1})
        wd.close()
    return SyncRun(losses=losses, step_seconds=step_s,
                   step_compiles=compiles[0], compile_seconds=compiles[1],
                   state=state, mesh=mesh, build=build)


def run_ps_mode(args) -> list:
    """--mode ps: execute algorithms on the real parameter-server runtime
    and cross-check the measured clock against the calibrated DES."""
    import dataclasses as _dc

    from repro import ps
    from repro.core import costmodel

    from repro.ps import zoo

    algos = (list(ps.ALGORITHMS) if args.algorithm == "all"
             else [args.algorithm])
    easgd = EASGDConfig(eta=args.eta, rho=args.rho, mu=0.9, tau=args.tau)
    net = costmodel.PS_WIRE if args.emulate == "wire" else None
    wire_codec = args.compression if args.transport == "tcp" else "none"
    if wire_codec not in ("none", "sign_ef"):
        raise SystemExit(
            f"--mode ps --transport tcp supports wire compression "
            f"none|sign_ef, got '{wire_codec}'")
    if args.sync_plane == "p2p" and args.transport != "tcp":
        raise SystemExit("--sync-plane p2p needs --transport tcp (the p2p "
                         "data plane is worker↔worker sockets)")
    problem = zoo.resolve(args.model)
    topology = None
    if args.topology:
        from repro.core.easgd_flat import SYNC_FAMILY as _SYNC_T
        try:
            hosts, slots = (int(x) for x in args.topology.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--topology wants HOSTSxSLOTS (e.g. 2x8), "
                             f"got '{args.topology}'")
        if hosts * slots != args.ps_workers:
            raise SystemExit(f"--topology {hosts}x{slots} does not tile "
                             f"--ps-workers {args.ps_workers}")
        if args.transport not in ("thread", "tcp"):
            raise SystemExit("--topology needs --transport thread or tcp "
                             "(per-link pacing lives on those planes)")
        topology = costmodel.emulated_topology(
            hosts, slots, cross_alpha_x=args.cross_alpha_x,
            cross_beta_x=args.cross_beta_x)
        algos = [a for a in algos if a in _SYNC_T]
        if not algos:
            raise SystemExit("--topology prices the sync-family exchange — "
                             "pick a sync_* algorithm (or 'all')")
        net = None      # topology REPLACES the global emulated wire
    base = ps.PSConfig(
        algorithm=algos[0], n_workers=args.ps_workers,
        transport=args.transport, schedule=args.schedule or "ring",
        total_iters=args.ps_iters, eval_every_iters=args.ps_eval_every,
        emulate_net=net, wire_compression=wire_codec,
        bucket_bytes=args.bucket_bytes, overlap=not args.no_overlap,
        topology=topology,
        trace=args.trace or bool(args.trace_dir),
        trace_dir=args.trace_dir)
    cal = ps.calibrate(problem, base)
    out = []
    from repro.core.easgd_flat import SYNC_FAMILY as _SYNC
    for algo in algos:
        # the p2p plane only exists for the sync family; `--algorithm all
        # --sync-plane p2p` runs the rest through the master as usual —
        # and the fused-kernel update path rides the p2p worker loop only
        plane = args.sync_plane if algo in _SYNC else "master"
        backend = args.update_backend if plane == "p2p" else "numpy"
        cfg = _dc.replace(base, algorithm=algo, sync_plane=plane,
                          update_backend=backend)
        res, _, rec = ps.run_vs_des(problem, easgd, cfg, cal=cal)
        print(f"{algo:16s} [{res.transport}/{res.schedule}] "
              f"iters={res.total_iters} err={res.final_metric:.3f} "
              f"measured={rec['measured_us_per_iter']:.1f}us/iter "
              f"des={rec['des_us_per_iter']:.1f}us/iter "
              f"ratio={rec['measured_over_des']:.2f} "
              f"counters={res.counters}", flush=True)
        if res.trace is not None:
            _report_trace(res, algo, args.trace_dir)
        out.append(res)
    return out


def _report_trace(res, algo: str, trace_dir) -> None:
    """Write the merged Chrome trace next to the run and print the measured
    time breakdown (open the .json at https://ui.perfetto.dev)."""
    import os as _os

    from repro.obs import report as obs_report

    rep = res.trace.get("report", {})
    out_dir = trace_dir or "."
    path = _os.path.join(out_dir, f"trace-{algo}-{res.transport}.json")
    obs_report.write_chrome_trace(path, res.trace)
    print(f"{algo:16s} trace: comm={rep.get('mean_comm_share', 0):.1%} "
          f"compute={rep.get('mean_compute_share', 0):.1%} "
          f"update={rep.get('mean_update_share', 0):.1%} -> {path}",
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="sync", choices=["sync", "ps"],
                    help="sync: jitted multi-pod Sync-EASGD (default); "
                         "ps: real parameter-server runtime (repro.ps)")
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8,
                    help="global batch (sequences)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--n-pods", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--eta", type=float, default=0.02)
    ap.add_argument("--rho", type=float, default=0.01)
    ap.add_argument("--tau", type=int, default=1)
    ap.add_argument("--schedule", default=None,
                    choices=list(comm.names()) + ["auto"],
                    help="cross-pod exchange schedule (repro.comm registry; "
                         "'auto' picks via comm.choose from buffer size and "
                         "pod count at build time). Default: psum in sync "
                         "mode, ring in ps mode")
    # --mode ps options (repro.ps runtime)
    ap.add_argument("--algorithm", default="all",
                    help="ps algorithm (core.async_engine.ALGORITHMS) or "
                         "'all'")
    ap.add_argument("--transport", default="thread",
                    choices=["thread", "process", "tcp"],
                    help="ps worker substrate: in-process threads, spawned "
                         "multiprocessing on shared memory, or the "
                         "repro.net TCP transport (real sockets; "
                         "launch/cluster adds multi-host)")
    ap.add_argument("--ps-workers", type=int, default=4)
    ap.add_argument("--ps-iters", type=int, default=400)
    ap.add_argument("--ps-eval-every", type=int, default=200)
    ap.add_argument("--model", default="tiny-mlp",
                    help="ps training problem (repro.ps.zoo): tiny-mlp "
                         "(default, unchanged), mlp-large, jax-mlp, lenet, "
                         "alexnet, or any repro.configs arch id (e.g. "
                         "gemma3-4b — a real reduced-config LM on the wire)")
    ap.add_argument("--bucket-bytes", type=int, default=0,
                    help="bucket the sync-family exchange into ~this many "
                         "payload bytes per bucket, cut at layer edges "
                         "(0 = monolithic). With the tcp p2p plane buckets "
                         "stream while compute runs — bitwise-identical "
                         "math, overlapped wire")
    ap.add_argument("--update-backend", default="numpy",
                    choices=["numpy", "pallas"],
                    help="p2p per-bucket update path: easgd_flat numpy or "
                         "the fused Pallas elastic-update kernel (bitwise "
                         "under the worker's pinned XLA flags)")
    ap.add_argument("--sync-plane", default="master",
                    choices=["master", "p2p"],
                    help="tcp sync family: 'p2p' executes Schedule.rounds "
                         "over direct worker↔worker links (the master "
                         "becomes control plane — see repro.net.peer)")
    ap.add_argument("--topology", default=None, metavar="HOSTSxSLOTS",
                    help="ps sync family: emulate a two-level fabric (e.g. "
                         "2x8 = 2 hosts x 8 slots; HOSTSxSLOTS must equal "
                         "--ps-workers). Cross-host links cost "
                         "--cross-alpha-x/--cross-beta-x times the "
                         "intra-host wire; pacing, schedule choice "
                         "(--schedule auto) and byte counters all become "
                         "per-link-class. Replaces --emulate")
    ap.add_argument("--cross-alpha-x", type=float, default=20.0,
                    help="cross-host latency multiplier for --topology "
                         "(default 20)")
    ap.add_argument("--cross-beta-x", type=float, default=4.0,
                    help="cross-host inverse-bandwidth multiplier for "
                         "--topology (default 4)")
    ap.add_argument("--emulate", default="wire", choices=["wire", "none"],
                    help="ps wire emulation: 'wire' sleeps each message's "
                         "α+nβ under costmodel.PS_WIRE (paper's regime); "
                         "'none' uses raw shared memory")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable compute/comm overlap (Sync EASGD1/2 "
                         "baseline, paper §6.1.3)")
    ap.add_argument("--trace", action="store_true",
                    help="record per-thread spans in every worker "
                         "(repro.obs), merge them onto the master clock, "
                         "and write a Perfetto-loadable trace-<algo>.json "
                         "plus a measured comm/compute/update breakdown")
    ap.add_argument("--trace-dir", default=None,
                    help="directory for trace spill files and the merged "
                         "trace JSON (implies --trace; default: BYE frames "
                         "carry buffers in-band, trace written to cwd)")
    ap.add_argument("--compression", default="none")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    if args.mode == "ps":
        return run_ps_mode(args)

    use_compile_cache()
    args.schedule = args.schedule or "psum"
    spec = configs.get(args.arch)
    cfg = spec.reduced if args.reduced else spec.config
    ecfg = ElasticConfig(
        easgd=EASGDConfig(eta=args.eta, rho=args.rho, mu=0.9, tau=args.tau),
        schedule=args.schedule,
        overlap=not args.no_overlap,
        compression=args.compression,
        momentum_dtype=spec.momentum_dtype,
        center_dtype=spec.center_dtype,
    )
    print(f"exchange: schedule={args.schedule} "
          f"compression={args.compression} "
          f"overlap={not args.no_overlap} n_pods={args.n_pods}", flush=True)
    run = run_sync(cfg, ecfg, steps=args.steps, batch=args.batch,
                   seq=args.seq, n_pods=args.n_pods,
                   microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
                   ckpt_every=args.ckpt_every, log_every=args.log_every)
    losses = run.losses
    if len(losses) > 10:
        first = np.mean(losses[:5])
        last = np.mean(losses[-5:])
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    main()
