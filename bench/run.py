#!/usr/bin/env python3
"""Chip benchmark of the Sync-EASGD training step.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``, the numbers that decided ``correct``
beside their limits. Exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell asks for.

One run: set-up builds the step with ``runtime.train.build_train_step`` on
a mesh from ``launch.mesh.make_host_mesh``, feeds it through
``data.ShardedPipeline`` with the traffic mix's rows, and drives that step
through its first ``check_steps`` steps (which also warm it). The same
state then runs the timed window: steps until ``--seconds`` have passed,
each step as ``launch.train.run_sync`` makes it (put the batch, run the
step, read its loss). With ``--trace 1`` the window runs under the
profiler and the cell's per-layer metrics are read from the trace. After
the window the program's state is freed and the plain reference
(``reference.py``) follows the same first steps from the same seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, gen, spec, tracing  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GIB = 2.0 ** 30


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the machine
# ---------------------------------------------------------------------------

def tpu_devices(n: int):
    """The first ``n`` TPU devices; exits 1 without them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: needs a TPU, but JAX found platform "
            f"'{devs[0].platform}'")
        sys.exit(1)
    if len(devs) < n:
        log(f"bench: the cell asks for {n} chips, JAX found {len(devs)}")
        sys.exit(1)
    return devs[:n]


def use_compile_cache() -> None:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads it), else ``<checkout>/.jax_cache``, a fixed path. Every program
    is cached, however fast it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@contextlib.contextmanager
def count_compiles():
    """Yields ``[n]``: backend compiles (or persistent-cache loads) of any
    function while the block runs. A copy of ``launch.train.count_compiles``
    that counts every function."""
    import jax
    seen = [0]

    def on_event(event, secs, **kw):
        if event == COMPILE_EVENT:
            seen[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def program_config(conf: dict):
    """The program's registered configuration of ``conf["arch"]`` (its
    ``preset``, "config" unless the file names "reduced") with the file's
    depth; every other size in the file must equal the program's."""
    import jax.numpy as jnp

    from repro import configs
    base = getattr(configs.get(conf["arch"]), conf.get("preset", "config"))
    cfg = dataclasses.replace(base, n_layers=conf["model"]["n_layers"])
    for key, want in conf["model"].items():
        have = getattr(cfg, key)
        if key == "ssm":
            have = dataclasses.asdict(have)
        elif key in ("param_dtype", "compute_dtype"):
            have = jnp.dtype(have).name
        elif isinstance(have, tuple):
            have = list(have)
        if have != want:
            raise ValueError(f"{conf['name']}: the program's {key} is "
                             f"{have!r}, the configuration's {want!r}")
    return cfg


def elastic_config(traffic: dict):
    from repro.core.easgd import EASGDConfig
    from repro.core.elastic import ElasticConfig
    return ElasticConfig(
        easgd=EASGDConfig(eta=traffic["eta"], rho=traffic["rho"],
                          mu=traffic["mu"], tau=traffic["tau"]),
        schedule=traffic["schedule"], packed=traffic["packed"],
        overlap=traffic["overlap"])


def keyed_init(cfg, ecfg, mesh, build):
    """``build.init_state`` with the key as an argument. The program's
    ``init_state`` holds its seed as a constant, so every new seed would
    compile it again in set-up; this is the same initialisation (the
    program's ``init_params`` and ``elastic.init``, the step's shardings),
    compiled once for all seeds."""
    from functools import partial

    import jax

    from repro.core import elastic
    from repro.models import transformer as tfm
    from repro.models.common import init_params
    from repro.runtime import sharding as shd
    defs = tfm.model_defs(cfg)

    @partial(jax.jit, out_shardings=shd.named(mesh, build.state_specs))
    def init_state(key):
        return elastic.init(init_params(defs, key, cfg.param_dtype), ecfg,
                            build.n_pods)
    return init_state


class Cell:
    """One cell's program: mesh, step, state and feed, as
    ``launch.train.run_sync`` assembles them."""

    def __init__(self, conf, traffic, devices, seed):
        import jax

        from repro.data import ShardedPipeline
        from repro.launch.mesh import make_host_mesh
        from repro.runtime import sharding as shd
        from repro.runtime.train import build_train_step

        self.cfg = program_config(conf)
        self.traffic = traffic
        P = traffic["workers"]
        n = len(devices)
        if P > 1:
            mesh = make_host_mesh((P, max(1, n // P), 1),
                                  ("pod", "data", "model"), devices=devices)
        else:
            mesh = make_host_mesh((n, 1), ("data", "model"), devices=devices)
        ecfg = elastic_config(traffic)
        self.build = build_train_step(
            self.cfg, ecfg, mesh, n_pods=P,
            per_pod_batch=traffic["batch_per_worker"], seq=traffic["seq"],
            seed=seed % 2**32)
        self.batch_shardings = shd.named(mesh, self.build.batch_spec_tree)
        self.state = keyed_init(self.cfg, ecfg, mesh, self.build)(
            jax.random.PRNGKey(seed % 2**32))
        V, S, B = self.cfg.vocab_size, traffic["seq"], \
            traffic["batch_per_worker"]
        self.pipe = ShardedPipeline(
            lambda shard, k: gen.MarkovLM(traffic["data"], V, S, B, seed,
                                          shard, k),
            n_pods=P).start()
        self._annotate = jax.profiler.TraceAnnotation

    def step(self):
        """One step as ``run_sync`` makes it; returns its loss."""
        import jax
        ann = self._annotate
        with ann(tracing.STEP_SPAN):
            with ann("bench.input"):
                batch = jax.device_put(self.pipe.next(), self.batch_shardings)
            with ann("bench.dispatch"):
                self.state, metrics = self.build.step(self.state, batch)
            with ann("bench.loss_read"):
                jax.block_until_ready((self.state, metrics))
                return float(metrics["loss"])

    def close(self):
        self.pipe.stop()
        self.state = None


def _leaf_norms(tree):
    """Per leaf (in flattening order), the norm of each worker's slice."""
    import jax
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), 1))
            for x in jax.tree_util.tree_leaves(tree)]


def grad_readings(cell: Cell):
    """After the first step, V_1 = -eta * g: per worker, per leaf, the norm
    of the gradient the optimizer got."""
    import jax
    eta = cell.traffic["eta"]
    norms = jax.jit(lambda v: [n / eta for n in _leaf_norms(v)])
    return [list(r) for r in zip(*jax.device_get(norms(cell.state.momentum)))]


def change_readings(cell: Cell, layout, key):
    """After the checked steps: per worker and leaf, the norm of the
    weights' change from the stated initialisation, and per leaf the
    center's."""
    import jax
    import jax.numpy as jnp

    from bench import reference
    leaves = jax.tree_util.tree_leaves(cell.state.params)
    shapes = [tuple(x.shape[1:]) for x in leaves]
    if shapes != [tuple(s) for _, s, _ in layout]:
        raise ValueError(f"the program's leaves {shapes} are not the "
                         f"configuration's layout")
    names = [n for n, _, _ in layout]

    @jax.jit
    def change(params, center, key):
        w0 = reference.init_params(layout, key)
        p, c = jax.tree_util.tree_leaves(params), \
            jax.tree_util.tree_leaves(center)
        return (_leaf_norms([x - w0[k][None] for x, k in zip(p, names)]),
                [jnp.sqrt(jnp.sum(jnp.square(x - w0[k])))
                 for x, k in zip(c, names)])

    ch, cc = jax.device_get(change(cell.state.params, cell.state.center,
                                   key))
    return [list(r) for r in zip(*ch)], [float(v) for v in cc]


def checked_steps(cell: Cell, layout, key) -> dict:
    """The first ``check_steps`` steps, through the window's own call and
    feed, and the program's readings that ``check`` compares."""
    prog = {"loss": []}
    for k in range(cell.traffic["check_steps"]):
        prog["loss"].append(cell.step())
        if k == 0:
            prog["grad"] = grad_readings(cell)
    prog["change"], prog["center_change"] = change_readings(cell, layout, key)
    return prog


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def state_bytes(state, device) -> dict:
    """Bytes of the weights, momentum and center held on ``device``."""
    import jax

    def on(tree):
        return float(sum(s.data.nbytes
                         for x in jax.tree_util.tree_leaves(tree)
                         for s in x.addressable_shards if s.device == device))
    return {"params": on(state.params), "momentum": on(state.momentum),
            "center": on(state.center)}


def run_cell(conf, mod, traffic, limits, devices, seed, seconds, trace,
             metric_entries=(), peaks=None, t_start=T_START, keep_trace=None):
    """One run of a cell; returns the result object. ``metric_entries``
    are the per-layer metrics read with ``trace``; ``keep_trace`` is a
    directory that keeps the trace and the compiled step's text (the test
    fixtures are recorded so)."""
    import jax

    from bench import reference

    P = traffic["workers"]
    layout = mod.layout(conf["model"])
    key = jax.random.PRNGKey(seed % 2**32)
    cell = Cell(conf, traffic, devices, seed)
    prog = checked_steps(cell, layout, key)
    setup_s = time.perf_counter() - t_start
    log(f"bench: set-up {setup_s:.3f} s; checked losses {prog['loss']}")

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window_losses = []
    with count_compiles() as compiles:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tdir, profiler_options=opts)
        t0 = time.perf_counter()
        while True:
            window_losses.append(cell.step())
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
        if trace:
            jax.profiler.stop_trace()
    if compiles[0]:
        raise RuntimeError(f"{compiles[0]} compiles inside the window")
    window_s = t1 - t0
    n_steps = len(window_losses)
    failed = sum(not math.isfinite(x) for x in window_losses)
    log(f"bench: window {n_steps} steps in {window_s:.6f} s; last loss "
        f"{window_losses[-1]}")

    stats = [d.memory_stats() or {} for d in devices]
    peak_in_use = max(m.get("peak_bytes_in_use", 0) for m in stats)
    reserved = max(m.get("peak_bytes_reserved", 0) for m in stats)
    log(f"bench: memory_stats of the fullest device "
        f"{max(stats, key=lambda m: m.get('peak_bytes_in_use', 0))}")
    abstract = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        t)
    batch_abs = jax.tree_util.tree_map(
        lambda d, s: jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=s),
        _batch_defs(cell), cell.batch_shardings)
    compiled = cell.build.step.lower(abstract(cell.state), batch_abs).compile()
    ma = compiled.memory_analysis()
    aot = (ma.argument_size_in_bytes + ma.output_size_in_bytes
           - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    log(f"bench: peak_bytes_in_use {peak_in_use} B; peak_bytes_reserved "
        f"{reserved} B; memory_analysis {aot} B (arguments "
        f"{ma.argument_size_in_bytes} + outputs {ma.output_size_in_bytes} - "
        f"aliased {ma.alias_size_in_bytes} + temporaries "
        f"{ma.temp_size_in_bytes})")
    sbytes = state_bytes(cell.state, devices[0])
    hlo_text = compiled.as_text() if trace else None
    step_name = cell.build.step.__name__
    del compiled
    cell.close()
    del cell
    kind = devices[0].device_kind
    peaks = peaks or spec.peaks(kind)
    tokens_per_s = n_steps * P * traffic["batch_per_worker"] \
        * traffic["seq"] / window_s
    flops_per_token = mod.flops_per_token(conf["model"], traffic["seq"])
    chips = len(devices)

    result = {"correct": False, "attempted": n_steps, "failed": failed}
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": int(peak_in_use)}
    if trace:
        from jax.profiler import ProfileData
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        if keep_trace:
            _keep(keep_trace, path, hlo_text, step_name)
        reduced = tracing.reduce(ProfileData.from_file(path).planes,
                                 tracing.hlo_layers(hlo_text, step_name),
                                 step_name)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = {"trace": reduced, "workers": P, "chips": chips,
               "peaks": peaks, "state_bytes": sbytes,
               "flops_per_step_per_chip":
                   flops_per_token * P * traffic["batch_per_worker"]
                   * traffic["seq"] / chips}
        metrics = {}
        for m in metric_entries:
            v = spec.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        log(f"bench: trace {reduced['steps']} steps; layers "
            f"{reduced['layer_s']}; per device "
            f"{[(d['name'], d['busy_s']) for d in reduced['devices']]}")
    else:
        metrics = {
            "tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"},
            "mfu": {"value": 100.0 * tokens_per_s * flops_per_token
                    / (chips * peaks["bf16_flops_per_s"]), "unit": "%"},
            "peak_hbm_gib": {"value": max(peak_in_use, aot) / GIB,
                             "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = reduced["breakdown"]

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    batches = [gen.worker_batches(traffic, conf["model"]["vocab_size"], seed,
                                  s) for s in range(traffic["check_steps"])]
    ref = reference.Reference(mod, conf["model"], traffic).run(
        key, batches, devices)
    values = check.gaps(prog, ref)
    correct, checks = check.judge(values, limits, failed)
    log(f"bench: reference {time.perf_counter() - t_ref:.1f} s; program "
        f"losses {prog['loss']}, reference {ref['loss']}")
    result["correct"] = correct
    result["checks"] = {k: {"value": _num(v["value"]), "limit": v["limit"]}
                        for k, v in checks.items()}
    for k, v in result["checks"].items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return result


def _keep(out_dir, xplane, hlo_text, step_name):
    import gzip
    os.makedirs(out_dir, exist_ok=True)
    with open(xplane, "rb") as f, \
            gzip.open(os.path.join(out_dir, "trace.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    with gzip.open(os.path.join(out_dir, "step.hlo.txt.gz"), "wt") as g:
        g.write(hlo_text)
    with open(os.path.join(out_dir, "step_name.txt"), "w") as g:
        g.write(step_name + "\n")


def _batch_defs(cell):
    from repro.runtime.train import make_batch_defs
    t = cell.traffic
    return make_batch_defs(cell.cfg, t["workers"], t["batch_per_worker"],
                           t["seq"])


def _num(v):
    return v if math.isfinite(v) else str(v)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    w = spec.workload(bench, args.workload)
    conf = spec.config(bench, w["config"])
    mod = spec.config_module(bench, w["config"])
    traffic = spec.traffic(w["traffic"])
    limits = spec.limits(w["name"])
    devices = tpu_devices(w["chips"])
    use_compile_cache()
    entries = spec.per_layer(bench, w["name"]) if args.trace else ()
    result = run_cell(conf, mod, traffic, limits, devices, args.seed,
                      args.seconds, bool(args.trace), entries)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
