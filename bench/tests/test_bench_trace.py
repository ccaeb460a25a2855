"""The reduction from a profiler trace to per-layer numbers: the compiled
step's text to layers, interval arithmetic, a synthetic trace with known
answers, and small traces recorded on a TPU v5e."""
import glob
import gzip
import os
from types import SimpleNamespace as NS

import pytest

from bench import tracing

HLO = """HloModule jit_sync_easgd_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(sync_easgd_step)/mul" stack_frame_id=3}
}

%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %exponential.1 = f32[4]{0} exponential(%param_0.1), metadata={op_name="jit(sync_easgd_step)/vmap(jvp())/exp"}
}

ENTRY %main.9 (p: f32[4]) -> (f32[4], f32[4]) {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2
  %all-reduce-start.1 = (f32[4]{0}, f32[4]{0}) all-reduce-start(%fusion.1), replica_groups={{0,1}}, to_apply=%add
  %all-reduce-done.1 = f32[4]{0} all-reduce-done(%all-reduce-start.1)
  %copy.3 = f32[4]{0:T(128)} copy(%p)
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%all-reduce-done.1, %fusion.2)
}
"""


def test_parse_hlo_opcodes_and_names():
    parsed = tracing.parse_hlo(HLO)
    assert parsed["all-reduce-start.1"][0] == "all-reduce-start"
    assert parsed["tuple.1"][0] == "tuple"
    assert parsed["fusion.1"] == ("fusion", "jit(sync_easgd_step)/mul")
    assert parsed["copy.3"] == ("copy", None)


def test_layers_of_the_step():
    layers = tracing.hlo_layers(HLO, "sync_easgd_step")
    assert layers["fusion.1"][0] == "update"
    assert layers["fusion.2"][0] == "fwd_bwd"
    assert layers["all-reduce-start.1"][0] == "exchange"
    assert layers["all-reduce-done.1"][0] == "exchange"
    assert layers["copy.3"][0] == "fwd_bwd"


@pytest.mark.parametrize("opcode,op_name,layer", [
    ("all-gather-start", None, "exchange"),
    ("reduce-scatter", "jit(f)/vmap(x)", "exchange"),
    ("collective-permute-done", None, "exchange"),
    ("fusion", "jit(f)/sub", "update"),
    ("fusion", "jit(f)/vmap(transpose(jvp()))/while", "fwd_bwd"),
    ("fusion", "checkpoint/reduce_sum", "fwd_bwd"),
    ("fusion", None, "fwd_bwd"),
])
def test_layer_rule(opcode, op_name, layer):
    assert tracing.layer_of(opcode, op_name, "f") == layer


def test_intervals():
    u = tracing.union([[5, 7], [0, 2], [1, 3], [7, 8]])
    assert u == [[0, 3], [5, 8]]
    assert tracing.length(u) == 6
    assert tracing.intersect(u, [[2, 6]]) == [[2, 3], [5, 6]]
    assert tracing.clip(u, 1, 6) == [[1, 3], [5, 6]]


def test_loop_ops_count_their_own_time():
    ops = [(0, 100, "fwd_bwd", "while.1", None),
           (10, 20, "fwd_bwd", "fusion.1", None),
           (30, 90, "update", "fusion.2", None),
           (100, 110, "update", "fusion.3", None)]
    own = {o[3]: (o[5], o[6]) for o in tracing._self_times(ops)}
    assert own == {"while.1": (30, False), "fusion.1": (10, True),
                   "fusion.2": (60, True), "fusion.3": (10, True)}


def _ev(name, s, e):
    return NS(name=name, start_ns=float(s), duration_ns=float(e - s))


def _synthetic():
    ops = [_ev("%fusion.2 = f32[4] fusion(%p)", 0, 100),
           _ev("%all-reduce-start.1 = (f32[4]) all-reduce-start(%x)",
               100, 101),
           _ev("%fusion.2 = f32[4] fusion(%p)", 101, 200),
           _ev("%all-reduce-done.1 = f32[4] all-reduce-done(%y)", 200, 230),
           _ev("%fusion.1 = f32[4] fusion(%p)", 230, 260)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules",
           events=[_ev("jit_sync_easgd_step(123)", 0, 260)]),
        NS(name="XLA Ops", events=ops),
        NS(name="Async XLA Ops", events=[
            _ev("%all-reduce-start.1 = (f32[4]) all-reduce-start(%x)",
                100, 230)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.step", 0, 300), _ev("bench.input", 0, 5),
        _ev("bench.dispatch", 5, 10), _ev("bench.loss_read", 10, 300)])])
    return [host, device]


def test_reduce_synthetic():
    layers = tracing.hlo_layers(HLO, "sync_easgd_step")
    r = tracing.reduce(_synthetic(), layers, "sync_easgd_step")
    assert r["steps"] == 1
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["busy_s"] == pytest.approx(260e-9)
    assert r["layer_s"]["fwd_bwd"] == pytest.approx(199e-9)
    assert r["layer_s"]["update"] == pytest.approx(30e-9)
    assert r["layer_s"]["exchange"] == pytest.approx(31e-9)
    assert r["exchange_s"] == pytest.approx(130e-9)
    # [100, 101] and [200, 230] run beside no other op
    assert r["exchange_exposed_s"] == pytest.approx(31e-9)
    assert r["host_s"]["bench.input"] == pytest.approx(5e-9)
    gap = r["breakdown"]["idle_gaps"][0]
    assert gap[0].endswith("bench.loss_read")
    assert gap[1] == pytest.approx(40e-9)
    top = r["breakdown"]["device_ops"][0]
    assert top[0].startswith("fwd_bwd fusion.2")


def test_reduce_refuses_a_trace_without_steps():
    with pytest.raises(ValueError):
        tracing.reduce([], {}, "sync_easgd_step")


FIXTURES = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "fixture_*")))


def _load(fixture):
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(fixture, "trace.xplane.pb.gz"), "rb") as f:
        planes = ProfileData.from_serialized_xspace(f.read()).planes
    with gzip.open(os.path.join(fixture, "step.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    with open(os.path.join(fixture, "step_name.txt")) as f:
        step = f.read().strip()
    return planes, tracing.hlo_layers(hlo, step), step


@pytest.mark.parametrize("fixture", FIXTURES, ids=os.path.basename)
def test_recorded_trace(fixture):
    planes, layers, step = _load(fixture)
    r = tracing.reduce(planes, layers, step)
    n_chips = int(os.path.basename(fixture).split("_")[1][0])
    assert r["n_devices"] == n_chips and r["steps"] >= 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["layer_s"]["fwd_bwd"] > 0 and r["layer_s"]["update"] > 0
    assert sum(r["layer_s"].values()) == pytest.approx(
        sum(sum(d["layer_s"].values()) for d in r["devices"]) / n_chips)
    # loop ops span their bodies' ops: counted by their own time, the
    # layers add up to the busy time
    assert sum(r["layer_s"].values()) == pytest.approx(r["busy_s"], rel=1e-3)
    assert r["exchange_exposed_s"] <= r["exchange_s"] + 1e-12
    if n_chips > 1:
        assert r["exchange_s"] > 0
    else:
        assert r["exchange_s"] == 0
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert len(r["breakdown"]["idle_gaps"]) <= 10
    for name in ("bench.input", "bench.dispatch", "bench.loss_read"):
        assert r["host_s"][name] > 0


def test_step_mfu_reads_device_busy_time():
    from bench import spec
    layers = tracing.hlo_layers(HLO, "sync_easgd_step")
    r = tracing.reduce(_synthetic(), layers, "sync_easgd_step")
    ctx = {"trace": r, "peaks": {"bf16_flops_per_s": 1e12},
           "flops_per_step_per_chip": 52e3}
    # 52e3 FLOPs in 260 ns busy at 1e12 FLOP/s: 20%, whatever the 40 ns
    # of idle in the 300 ns window
    assert spec.reader("step_mfu").read(ctx) == pytest.approx(20.0)
