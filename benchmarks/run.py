"""Benchmark runner: one module per paper table/figure (+ the
roofline table). Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig6_8,...]
                                            [--json]

``--json`` additionally writes one ``BENCH_<name>.json`` per module at the
repo root (rows + status + wall time) so the perf trajectory across PRs is
machine-readable, and appends the same record to
``bench_history/<name>/<git-sha>.json`` — the trail
``python -m repro.obs.regress bench_history/<name>`` gates on.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from benchmarks import common

MODULES = (
    "fig6_8_convergence",   # Figs 6 & 8: the nine algorithms, error vs time
    "table3_breakdown",     # Table 3 / Fig 11: breakdown + 5.3x
    "fig10_packing",        # Fig 10: packed vs per-layer communication
    "fig12_partitioning",   # Fig 12: chip partitioning sweep
    "table4_weakscaling",   # Table 4: weak scaling to 4352 cores
    "roofline",             # §Roofline table from the dry-run JSONL
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<name>.json per module at repo root")
    ap.add_argument("--real", action="store_true",
                    help="forward real=True to modules whose main() takes "
                         "it (fig6_8, table4: execute on the repro.ps "
                         "runtime instead of modeling only)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    failures = []
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        print(f"# === benchmarks.{name} ===", flush=True)
        if args.json:
            common.begin_json_capture()
        ok = True
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            kw = {}
            if args.real:
                import inspect
                if "real" in inspect.signature(mod.main).parameters:
                    kw["real"] = True
            mod.main(quick=args.quick, **kw)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            ok = False
            failures.append(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()}", flush=True)
        if args.json:
            rows, module_meta = common.end_json_capture()
            rec = {"module": name, "ok": ok, "quick": args.quick,
                   "elapsed_s": round(time.time() - t0, 3),
                   "meta": {**common.run_metadata(), **module_meta},
                   "rows": rows}
            path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"# wrote {path}", flush=True)
            # history trail for the regression gate: one file per commit,
            # newest-two compared by `repro.obs.regress bench_history/...`
            sha = str(rec["meta"].get("git_sha") or "nosha")[:12]
            hist_dir = os.path.join(REPO_ROOT, "bench_history", name)
            os.makedirs(hist_dir, exist_ok=True)
            hist_path = os.path.join(hist_dir, f"{sha}.json")
            with open(hist_path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"# appended {hist_path}", flush=True)
    if failures:
        print(f"# FAILURES: {failures}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
