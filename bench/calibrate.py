#!/usr/bin/env python3
"""The readings that a cell's limits are set from, at the cell's own size,
in one process on the chip:

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 --out <file.json>

For each seed: the program's checked steps (no timed window) and their gaps
to the float32 reference, as ``run.py`` computes them, each judged by the
cell's committed limits (``program_correct``, ``control_correct``). For
each control seed also the gaps of the float8 control and of the planted
faults, each the reference put in the program's place: "half" (the mean
over half of each worker's rows) and, with more than one worker,
"noexchange" (no sum across workers). A state left unchanged reads 1 and
needs no run. ``--control-only`` reads the control alone.
``--fixture <dir>`` instead records a short traced run of the cell at the
program's reduced widths, the trace that the tests of ``tracing`` read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import check, gen, run, spec  # noqa: E402
from bench.reference import Reference  # noqa: E402


def readings(conf, mod, traffic, limits, devices, seed, control, out,
             program=True):
    import jax
    key = jax.random.PRNGKey(seed % 2**32)
    layout = mod.layout(conf["model"])
    row = {"seed": seed}
    if program:
        t = time.perf_counter()
        cell = run.Cell(conf, traffic, devices, seed)
        prog = run.checked_steps(cell, layout, key)
        cell.close()
        del cell
        row["program_s"] = time.perf_counter() - t
    batches = [gen.worker_batches(traffic, conf["model"]["vocab_size"], seed,
                                  s) for s in range(traffic["check_steps"])]
    t = time.perf_counter()
    ref = Reference(mod, conf["model"], traffic).run(key, batches, devices)
    row["reference_s"] = time.perf_counter() - t
    row["reference_loss"] = ref["loss"]
    if program:
        row["program"] = check.gaps(prog, ref)
        row["program_correct"], _ = check.judge(row["program"], limits, 0)
        row["program_loss"] = prog["loss"]
    if control:
        ctrl = Reference(mod, conf["model"], traffic, "fp8").run(
            key, batches, devices)
        row["control"] = check.gaps(ctrl, ref)
        row["control_correct"], _ = check.judge(row["control"], limits, 0)
        row["control_loss"] = ctrl["loss"]
        faults = [] if control == "only" else \
            ["half"] + (["noexchange"] if traffic["workers"] > 1 else [])
        for f in faults:
            row[f] = check.gaps(Reference(mod, conf["model"], traffic).run(
                key, batches, devices, fault=f), ref)
    out.append(row)
    print(json.dumps(row), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--fixture", default=None)
    ap.add_argument("--control-only", action="store_true",
                    help="the float8 control on --seeds, without the program "
                         "or the faults")
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    w = spec.workload(bench, args.workload)
    conf = spec.config(bench, w["config"])
    mod = spec.config_module(bench, w["config"])
    traffic = spec.traffic(w["traffic"])
    devices = run.tpu_devices(w["chips"])
    run.use_compile_cache()
    if args.fixture:
        tiny = spec.load_json(os.path.join(spec.HERE, "tests", "data",
                                           "phi3_tiny.json"))
        small = dict(traffic, seq=64, batch_per_worker=2)
        lim = dict.fromkeys(check.NAMES, 1.0)
        res = run.run_cell(tiny, mod, small, lim, devices, 5, 0.05, True,
                           spec.per_layer(bench, w["name"]),
                           keep_trace=args.fixture)
        print(json.dumps(res), flush=True)
        return
    seeds = [int(s) for s in args.seeds.split(",") if s]
    limits = spec.limits(w["name"])
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = []
    for seed in seeds:
        if args.control_only:
            readings(conf, mod, traffic, limits, devices, seed, "only", out,
                     program=False)
        else:
            readings(conf, mod, traffic, limits, devices, seed,
                     seed in control, out)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
