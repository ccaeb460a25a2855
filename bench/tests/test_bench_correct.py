"""What decides ``correct`` fails when it should, at a size a test run
holds: the float8 control in the program's place, and whole runs of the
harness on the CPU with the timed path broken underneath (see
``fault_cases.py``), each judged by its cell's own limits."""
import concurrent.futures
import json
import os
import subprocess
import sys

import pytest

from bench import check, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CASES = [("1chip", f) for f in ("none", "unchanged", "half", "answer")] + \
        [("4chip", f) for f in ("none", "unchanged", "half", "noexchange",
                                "answer")]


@pytest.fixture(scope="module")
def outcomes(tmp_path_factory):
    """Every case, each in a process of its own with four CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("jax_cache")))

    def one(case):
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "fault_cases.py"), *case],
            env=env, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-4000:]
        return case, json.loads(p.stdout.strip().splitlines()[-1])

    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        return dict(pool.map(one, CASES))


@pytest.mark.parametrize("case", [c for c in CASES if c[1] != "none"],
                         ids="-".join)
def test_fault_is_not_correct(outcomes, case):
    out = outcomes[case]
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", ["1chip", "4chip"])
def test_faults_read_above_the_intact_run(outcomes, cell):
    intact = outcomes[(cell, "none")]["checks"]
    for (c, fault), out in outcomes.items():
        if c != cell or fault == "none":
            continue
        assert any(out["checks"][k]["value"] > intact[k]["value"]
                   for k in intact), (fault, out["checks"])


@pytest.mark.parametrize("workload,traffic", [
    ("phi3_b8s4096_1chip", "b8_s4096_1w"),
    ("phi3_b8s4096_4chip_psum", "b8_s4096_4w_psum")])
def test_fp8_control_is_not_correct(workload, traffic):
    import jax

    from bench import gen
    from bench.reference import Reference

    conf = spec.load_json(os.path.join(HERE, "data", "phi3_tiny.json"))
    mod = spec.config_module(spec.benchmark(), "phi3_mini")
    t = dict(spec.traffic(traffic), seq=64, batch_per_worker=2)
    key = jax.random.PRNGKey(11)
    batches = [gen.worker_batches(t, conf["model"]["vocab_size"], 11, s)
               for s in range(t["check_steps"])]
    dev = jax.devices()[:1]
    ref = Reference(mod, conf["model"], t).run(key, batches, dev)
    ctrl = Reference(mod, conf["model"], t, "fp8").run(key, batches, dev)
    correct, checks = check.judge(check.gaps(ctrl, ref),
                                  spec.limits(workload), 0)
    assert not correct, checks
