"""Finds what ``BENCHMARK.json`` names: each cell's configuration, traffic
mix, limits and per-layer readers, by name, under this directory. Imports
nothing of JAX, so tests and the harness's first checks stay cheap."""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}$")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _one(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    return _one(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    """The configuration's file, as it is run."""
    return load_json(os.path.join(root, _one(bench["configs"], name,
                                             "config")["file"]))


def config_module(bench: dict, name: str, root: str = ROOT):
    """The module beside the configuration's file: its plain reference,
    its leaves and ``flops_per_token``."""
    path = os.path.splitext(os.path.join(
        root, _one(bench["configs"], name, "config")["file"]))[0] + ".py"
    return _load_module(path, f"bench_config_{name}")


def traffic(name: str) -> dict:
    return load_json(os.path.join(HERE, "traffic", name + ".json"))


def limits(workload_name: str) -> dict:
    """The limit of each number that decides ``correct`` in this cell."""
    return load_json(os.path.join(HERE, "limits", workload_name + ".json"))


def peaks(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} has no peaks in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]


def per_layer(bench: dict, workload_name: str) -> list:
    """The per-layer metrics that this cell reports."""
    return [m for m in bench["per_layer"]
            if workload_name in m.get("workloads", [workload_name])]


def end_to_end(bench: dict, workload_name: str) -> list:
    return [m for m in bench["end_to_end"]
            if workload_name in m.get("workloads", [workload_name])]


def reader(metric_name: str):
    """``metrics/<name>.py``: ``read(ctx) -> float | None``."""
    return _load_module(os.path.join(HERE, "metrics", metric_name + ".py"),
                        f"bench_metric_{metric_name.replace('.', '_')}")


def _load_module(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
