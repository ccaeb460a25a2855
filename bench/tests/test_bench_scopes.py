"""The program's named scopes and ``repro.*`` host spans, and their
reduction (``bench/scopes.py``): where the scopes land in the compiled
step (4 CPU devices), what the existing layer rule makes of them, a
synthetic trace with known answers, and the traces recorded on a TPU v5e."""
import glob
import gzip
import os
import re
import subprocess
import sys
import textwrap
from types import SimpleNamespace as NS

import pytest

from bench import scopes, spec, tracing

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
STEP = "sync_easgd_step"
NEW = ("attention_ms", "mlp_ms", "loss_ms", "update_copy_ms",
       "input_queue_wait_ms", "idle_while_producing_ms")
MODEL_SCOPES = ("attention", "mlp", "loss")


@pytest.fixture(scope="module")
def step_hlo(tmp_path_factory):
    """The compiled text of the tiny phi3 step, 4 workers on 4 CPU
    devices, compiled in a process of its own."""
    out = tmp_path_factory.mktemp("hlo") / "step.hlo.txt"
    code = textwrap.dedent(f"""
        import jax
        from bench import run, spec
        from repro.launch.mesh import make_host_mesh
        from repro.runtime import sharding as shd
        from repro.runtime.train import build_train_step, make_batch_defs

        conf = spec.load_json({os.path.join(HERE, "data",
                                            "phi3_tiny.json")!r})
        t = dict(spec.traffic("b8_s4096_4w_psum"), seq=64,
                 batch_per_worker=2)
        cfg = run.program_config(conf)
        mesh = make_host_mesh((4, 1, 1), ("pod", "data", "model"),
                              devices=jax.devices()[:4])
        b = build_train_step(cfg, run.elastic_config(t), mesh, n_pods=4,
                             per_pod_batch=2, seq=64)
        shaped = lambda tree, specs: jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shd.named(mesh, specs))
        lowered = b.step.lower(
            shaped(b.abstract_state, b.state_specs),
            shaped(make_batch_defs(cfg, 4, 2, 64), b.batch_spec_tree))
        with open({str(out)!r}, "w") as f:
            f.write(lowered.compile().as_text())
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [ROOT, os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return out.read_text()


@pytest.fixture(scope="module")
def step_ops(step_hlo):
    """(instruction, opcode, op_name) of the step's ops that carry the
    step's name, the ops a trace can show."""
    return [(n, op, name)
            for n, (op, name) in tracing.parse_hlo(step_hlo).items()
            if name and name.startswith(f"jit({STEP})/")]


def test_every_scope_is_in_the_compiled_step(step_hlo):
    found = {scopes.scope_of(name)
             for _, name in tracing.parse_hlo(step_hlo).values()}
    assert set(scopes.SCOPES) <= found, set(scopes.SCOPES) - found


def test_no_segment_between_the_step_and_its_vmap(step_ops):
    """The layer rule reads "under the step, not under its vmap" as the
    update; a scope around the vmap would turn fwd/bwd into update."""
    prefix = f"jit({STEP})/"
    bad = [name for _, _, name in step_ops
           if "vmap(" in name and name.split("vmap(", 1)[0] != prefix]
    assert not bad, bad[:5]


def _unscoped(op_name):
    pat = "|".join(re.escape(s) for s in scopes.SCOPES)
    return re.sub(rf"(?<=/)(?:{pat})/", "", op_name)


def test_scopes_leave_every_layer_verdict(step_ops):
    """The existing layer rule gives each op the verdict it gives the op's
    name with the scopes taken out; scoped ops under the vmap are fwd/bwd,
    and the elastic phases update or exchange."""
    for n, op, name in step_ops:
        layer = tracing.layer_of(op, name, STEP)
        assert layer == tracing.layer_of(op, _unscoped(name), STEP), name
        scope = scopes.scope_of(name)
        if scope in MODEL_SCOPES and "vmap(" in name:
            assert layer == "fwd_bwd", (n, name)
        elif scope and scope.startswith("elastic."):
            assert layer in ("update", "exchange"), (n, name)
    model_ops = [name for _, _, name in step_ops
                 if scopes.scope_of(name) in MODEL_SCOPES]
    under_vmap = [name for name in model_ops if "vmap(" in name]
    # a few constants depend on no per-pod input and are lifted out of the
    # vmap (RoPE's tables); the rest is under it
    assert len(under_vmap) > 0.9 * len(model_ops)


def test_update_fusions_and_their_scopes(step_hlo):
    total, mixed = scopes.mixed_fusions(step_hlo, STEP)
    assert total > 0 and 0 <= mixed <= total


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/vmap(jvp(attention))/dot_general", "attention"),
    ("jit(f)/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/mul", "mlp"),
    ("jit(f)/vmap(jvp())/while/body/closed_call/loss", "loss"),
    ("jit(f)/shard_map/elastic.pack/concatenate", "elastic.pack"),
    ("jit(f)/attention/while/body/loss/exp", "loss"),
    ("jit(f)/vmap(jvp())/lossy/mul", None),
    ("jit(f)/attention_x/mul", None),
    ("jit(f)/elastic.packed/mul", None),
    ("jit(f)/vmap(jvp())/mul", None),
    (None, None),
])
def test_scope_is_a_whole_segment(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """HloModule jit_sync_easgd_step

%fused_a (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  ROOT %dot.1 = f32[4]{0} multiply(%p.1, %p.1), metadata={op_name="jit(sync_easgd_step)/vmap(jvp(attention))/dot_general"}
}

%fused_m (p.2: f32[4]) -> f32[4] {
  %p.2 = f32[4]{0} parameter(0)
  ROOT %mul.2 = f32[4]{0} multiply(%p.2, %p.2), metadata={op_name="jit(sync_easgd_step)/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/mlp/mul"}
}

%fused_p (p.3: f32[4]) -> f32[4] {
  %p.3 = f32[4]{0} parameter(0)
  ROOT %concatenate.3 = f32[4]{0} multiply(%p.3, %p.3), metadata={op_name="jit(sync_easgd_step)/shard_map/elastic.pack/concatenate"}
}

%fused_u (p.4: f32[4]) -> f32[4] {
  %p.4 = f32[4]{0} parameter(0)
  %slice.4 = f32[4]{0} negate(%p.4), metadata={op_name="jit(sync_easgd_step)/shard_map/elastic.unpack/slice"}
  ROOT %sub.4 = f32[4]{0} subtract(%slice.4, %p.4), metadata={op_name="jit(sync_easgd_step)/shard_map/elastic.update/sub"}
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.a = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_a
  %while.1 = f32[4]{0} while(%p), condition=%cond, body=%body, metadata={op_name="jit(sync_easgd_step)/vmap(jvp())/while/body/closed_call/loss/while"}
  %fusion.m = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_m
  %fusion.p = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_p
  %fusion.u = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_u
  ROOT %all-reduce.1 = f32[4]{0} all-reduce(%fusion.u), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(sync_easgd_step)/shard_map/elastic.exchange/psum"}
}
"""


def _ev(name, s, e):
    return NS(name=name, start_ns=float(s), duration_ns=float(e - s))


def _device(name, ops, end):
    return NS(name=name, lines=[
        NS(name="XLA Modules", events=[_ev("jit_sync_easgd_step(1)", 0,
                                           end)]),
        NS(name="XLA Ops", events=[_ev(f"%{n} = f32[4] op()", s, e)
                                   for n, s, e in ops])])


def _synthetic():
    """One 300 ns step. Device 0: attention 0-100, a loss loop 100-200
    holding an mlp op 120-150, pack 200-210, a fusion of unpack and update
    215-230 (it counts as its root, the update), the all-reduce 230-240,
    then idle. Device 1: attention 0-100 and 260-280.
    The producer thread runs 250-290 (and once before the window)."""
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[
            _ev("bench.step", 0, 300), _ev("bench.input", 0, 5),
            _ev("repro.input.wait", 1, 4), _ev("bench.dispatch", 5, 10),
            _ev("bench.loss_read", 10, 300)]),
        NS(name="python", events=[
            _ev("repro.input.produce", -50, -10),
            _ev("repro.input.produce", 250, 290)])])
    d0 = _device("/device:TPU:0", [
        ("fusion.a", 0, 100), ("while.1", 100, 200), ("fusion.m", 120, 150),
        ("fusion.p", 200, 210), ("fusion.u", 215, 230),
        ("all-reduce.1", 230, 240)], 240)
    d1 = _device("/device:TPU:1", [("fusion.a", 0, 100),
                                   ("fusion.a", 260, 280)], 280)
    return [host, d0, d1]


def test_reduce_synthetic():
    r = scopes.reduce(_synthetic(), HLO, STEP)
    want = {"attention": (100 + 120) / 2, "loss": 70 / 2, "mlp": 30 / 2,
            "elastic.pack": 10 / 2, "elastic.update": 15 / 2,
            "elastic.exchange": 10 / 2}
    assert r["scope_s"] == pytest.approx({k: v * 1e-9
                                          for k, v in want.items()})
    assert r["program_host_s"] == pytest.approx(
        {"repro.input.wait": 3e-9, "repro.input.produce": 40e-9})
    assert r["program_host_n"] == {"repro.input.wait": 1,
                                   "repro.input.produce": 1}
    # device 0 is idle 240-300, device 1 100-260 and 280-300: the producer
    # (250-290) overlaps 40 ns and 20 ns of it
    assert r["idle_producing_s"] == pytest.approx(30e-9)
    # tracing.reduce's keys as they are
    base = tracing.reduce(_synthetic(), tracing.hlo_layers(HLO, STEP), STEP)
    assert {k: r[k] for k in base} == base


def test_readers_on_the_synthetic_trace():
    ctx = {"trace": scopes.reduce(_synthetic(), HLO, STEP)}
    got = {m: spec.reader(m).read(ctx) for m in NEW}
    assert got == pytest.approx({
        "attention_ms": 110e-6, "mlp_ms": 15e-6, "loss_ms": 35e-6,
        "update_copy_ms": 5e-6, "input_queue_wait_ms": 3e-6,
        "idle_while_producing_ms": 30e-6})


def test_mixed_fusions_synthetic():
    # fusion.u holds unpack and update ops; fusion.p only pack
    assert scopes.mixed_fusions(HLO, STEP) == (2, 1)


def _load(fixture):
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(fixture, "trace.xplane.pb.gz"), "rb") as f:
        planes = ProfileData.from_serialized_xspace(f.read()).planes
    with gzip.open(os.path.join(fixture, "step.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    with open(os.path.join(fixture, "step_name.txt")) as f:
        step = f.read().strip()
    return list(planes), hlo, step


def test_old_fixture_has_no_program_names():
    """The trace recorded before the program named its layers: the
    tracing keys as they were, and every new reader returns None."""
    planes, hlo, step = _load(os.path.join(HERE, "data", "fixture_4chip"))
    r = scopes.reduce(planes, hlo, step)
    base = tracing.reduce(planes, tracing.hlo_layers(hlo, step), step)
    assert {k: r[k] for k in base} == base
    assert r["scope_s"] == {} and r["program_host_s"] == {}
    ctx = {"trace": r}
    assert {m: spec.reader(m).read(ctx) for m in NEW} == dict.fromkeys(NEW)
    # readers of a reduction without the added keys
    assert {m: spec.reader(m).read({"trace": base}) for m in NEW} \
        == dict.fromkeys(NEW)


SCOPED = sorted(glob.glob(os.path.join(HERE, "data", "fixture_*_scoped")))


@pytest.mark.parametrize("fixture", SCOPED, ids=os.path.basename)
def test_scoped_fixture_reads_the_new_metrics(fixture):
    planes, hlo, step = _load(fixture)
    r = scopes.reduce(planes, hlo, step)
    assert set(r["scope_s"]) == set(scopes.SCOPES)
    ctx = {"trace": r}
    got = {m: spec.reader(m).read(ctx) for m in NEW}
    assert all(v is not None for v in got.values()), got
    per_step = lambda s: 1e3 * s / r["steps"]
    assert got["attention_ms"] + got["mlp_ms"] + got["loss_ms"] \
        <= per_step(r["layer_s"]["fwd_bwd"])
    assert got["update_copy_ms"] <= per_step(r["layer_s"]["update"])
    assert 0 <= got["idle_while_producing_ms"] \
        <= per_step(r["window_s"] - r["busy_s"]) + 1e-9
    assert got["input_queue_wait_ms"] >= 0
    assert r["program_host_n"]["repro.input.produce"] >= 1
