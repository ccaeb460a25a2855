"""The ``mamba2_780m`` configuration through the harness on the CPU, at the
program's reduced widths: a whole run reads ``correct`` true, and false
with the "half" fault planted or with the float8 control; the mixer's named scopes ``ssm`` and
``ssm.scan`` on the compiled step; and the readers of their times."""
import os
import re

import jax
import pytest

from bench import check, gen, run, spec, tracing
from repro.launch.mesh import make_host_mesh
from repro.models import transformer
from repro.runtime.train import build_train_step, make_batch_defs

HERE = os.path.dirname(os.path.abspath(__file__))
CELL, TRAFFIC = "mamba2_b8s4096_1chip", "b8_s4096_1w"
TINY = {"seq": 64, "batch_per_worker": 2}
STEP = "sync_easgd_step"


def _tiny(name):
    return spec.load_json(os.path.join(HERE, "data", name + ".json"))


def _run(seed=2**31 + 7):
    return run.run_cell(_tiny("mamba2_tiny"),
                        spec.config_module(spec.benchmark(), "mamba2_780m"),
                        dict(spec.traffic(TRAFFIC), **TINY),
                        spec.limits(CELL), jax.devices()[:1], seed, 0.2,
                        False, peaks={"bf16_flops_per_s": 1e12,
                                      "hbm_bytes_per_s": 1e11})


def test_whole_run_is_correct():
    res = _run()
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["checks"]["grad"]["value"] < 0.01, res["checks"]


def test_half_fault_is_not_correct(monkeypatch):
    lm_loss = transformer.lm_loss

    def half(cfg, params, batch, extra_fwd_kwargs=None):
        n = batch["tokens"].shape[0] // 2
        return lm_loss(cfg, params, {k: v[:n] for k, v in batch.items()},
                       extra_fwd_kwargs)
    monkeypatch.setattr(transformer, "lm_loss", half)
    res = _run()
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_fp8_control_is_not_correct(seed):
    """The reference in float8 where the configuration states bfloat16,
    judged by the cell's limits, fails them."""
    from bench.reference import Reference

    conf = _tiny("mamba2_tiny")
    mod = spec.config_module(spec.benchmark(), "mamba2_780m")
    t = dict(spec.traffic(TRAFFIC), **TINY)
    key = jax.random.PRNGKey(seed % 2**32)
    batches = [gen.worker_batches(t, conf["model"]["vocab_size"], seed, s)
               for s in range(t["check_steps"])]
    dev = jax.devices()[:1]
    ref = Reference(mod, conf["model"], t).run(key, batches, dev)
    ctrl = Reference(mod, conf["model"], t, "fp8").run(key, batches, dev)
    correct, checks = check.judge(check.gaps(ctrl, ref), spec.limits(CELL),
                                  0)
    assert not correct, checks


def _compiled_step(conf_name, traffic_name):
    conf = _tiny(conf_name)
    t = dict(spec.traffic(traffic_name), **TINY)
    cfg = run.program_config(conf)
    mesh = make_host_mesh((1, 1), ("data", "model"),
                          devices=jax.devices()[:1])
    b = build_train_step(cfg, run.elastic_config(t), mesh, n_pods=1,
                         per_pod_batch=TINY["batch_per_worker"],
                         seq=TINY["seq"])
    return b.step.lower(b.abstract_state,
                        make_batch_defs(cfg, 1, TINY["batch_per_worker"],
                                        TINY["seq"])).compile().as_text()


def _segment(scope):
    return re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])")


@pytest.fixture(scope="module")
def mamba2_op_names():
    names = [n for _, n in tracing.parse_hlo(
        _compiled_step("mamba2_tiny", TRAFFIC)).values() if n]
    assert names
    return names


def test_scan_ops_carry_the_scan_scope(mamba2_op_names):
    """The scan's ops are under ``ssm.scan`` inside ``ssm``, in the forward
    pass, the backward pass and the backward pass's recomputation."""
    scan = [n for n in mamba2_op_names if _segment("ssm.scan").search(n)]
    assert all(_segment("ssm").search(n.split("ssm.scan")[0]) for n in scan)
    fwd = [n for n in scan if "transpose(" not in n]
    bwd = [n for n in scan if "transpose(" in n]
    remat = [n for n in scan if "rematted_computation" in n]
    assert fwd and bwd and remat
    # the scan's own ops: its einsums and the masked exponential
    assert any(n.endswith("/exp") for n in fwd)
    assert any(n.endswith("/dot_general") for n in fwd)


def test_projections_carry_the_mixer_scope(mamba2_op_names):
    """The mixer's projections are under ``ssm`` and outside the scan, and
    every op under the step's vmap that the scope names is fwd/bwd."""
    mixer = [n for n in mamba2_op_names if _segment("ssm").search(n)
             and not _segment("ssm.scan").search(n)]
    assert any(n.endswith("/dot_general") for n in mixer)
    for n in mixer:
        if n.startswith(f"jit({STEP})/vmap("):
            assert tracing.layer_of("fusion", n, STEP) == "fwd_bwd", n


def test_phi3_step_has_no_ssm_scope():
    """phi3's step runs nothing of ``models/ssm``: no op of its compiled
    step carries either scope."""
    names = [n for _, n in tracing.parse_hlo(
        _compiled_step("phi3_tiny", TRAFFIC)).values() if n]
    assert names
    assert not [n for n in names if _segment("ssm").search(n)
                or _segment("ssm.scan").search(n)]


@pytest.mark.parametrize("metric,scope", [("ssm_ms", "ssm"),
                                          ("ssm_scan_ms", "ssm.scan")])
def test_scope_readers(metric, scope):
    read = spec.reader(metric).read
    ctx = {"trace": {"steps": 4, "scope_s": {scope: 0.2, "loss": 1.0}}}
    assert read(ctx) == pytest.approx(50.0)
    assert read({"trace": {"steps": 4, "scope_s": {"loss": 1.0}}}) is None
    assert read({"trace": {"steps": 4}}) is None
