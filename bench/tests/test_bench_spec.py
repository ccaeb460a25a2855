"""BENCHMARK.json resolves: every cell, configuration, traffic mix, limit
file and per-layer reader is where the harness looks for it, and every
name, unit and key keeps to the benchmark's rules."""
import json
import os
import re

import pytest

from bench import check, spec

BENCH = spec.benchmark()
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LINE = re.compile(r"[^\t\n]{1,200}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj"
                   r"|head|expand|d_model|d_ff|experts_per_tok")


def test_top_level_keys_and_size():
    assert set(BENCH) == TOP
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)


def test_command_and_paths():
    assert 1 <= len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert LINE.match(word) and not word.startswith("/") \
            and ".." not in word
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert os.path.isfile(os.path.join(spec.ROOT, script))


def _names(entries):
    return [e["name"] for e in entries]


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_are_valid_and_unique(group):
    names = _names(BENCH[group])
    assert names and len(names) == len(set(names))
    for n in names:
        assert spec.NAME.match(n), n


def test_metric_names_unique_across_kinds():
    names = _names(BENCH["end_to_end"]) + _names(BENCH["per_layer"])
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_resolves(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert LINE.match(cfg["source"]) and LINE.match(cfg["why"])
    assert cfg["file"].startswith("bench/")
    conf = spec.config(BENCH, cfg["name"])
    assert conf["name"] == cfg["name"]
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert spec.NAME.match(key) and not WIDTH.search(key), key
        assert conf["published"][key] != conf["model"][key]
    mod = spec.config_module(BENCH, cfg["name"])
    for fn in ("layout", "loss_sum", "flops_per_token"):
        assert callable(getattr(mod, fn))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4)
    assert LINE.match(w["why"])
    assert spec.NAME.match(w["traffic"])
    assert w["config"] in _names(BENCH["configs"])
    traffic = spec.traffic(w["traffic"])
    assert traffic["workers"] * traffic["batch_per_worker"] >= 1
    assert w["chips"] % traffic["workers"] == 0
    limits = spec.limits(w["name"])
    compared = [k for k in check.NAMES if k in limits]
    assert compared and all(limits[k] > 0 for k in compared)
    assert "readings" in limits
    e2e = _names(spec.end_to_end(BENCH, w["name"]))
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer(BENCH, w["name"])


def test_pairs_unique_and_four_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert spec.UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25


def test_setup_bound():
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.25


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert spec.UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES and LINE.match(m["layer"])
    assert m["moves"] in _names(BENCH["end_to_end"])
    for w in m.get("workloads", []):
        assert w in _names(BENCH["workloads"])
        assert m["moves"] in _names(spec.end_to_end(BENCH, w))
    assert callable(spec.reader(m["name"]).read)
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_layers_spelled_alike():
    layers = {}
    for m in BENCH["per_layer"]:
        key = m["layer"].split(":")[0]
        layers.setdefault(key, set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_files_under_paths_are_named_from_name_characters():
    for dirpath, _, files in os.walk(os.path.join(spec.ROOT, "bench")):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), spec.ROOT)
            assert re.fullmatch(r"[A-Za-z0-9_./-]+", rel), rel


def test_peaks_table_refuses_unknown_devices():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_benchmark_is_plain_json():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        json.loads(f.read())


def test_refuses_to_run_without_a_tpu():
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3000000000", "--seconds",
         "1", "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
