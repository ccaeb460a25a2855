"""Ahead-of-time compiles for a described TPU v5e, at real widths.

Nothing here runs: each test lowers and compiles for a v5e chip that is
described, not attached, so the TPU compiler refuses here what it would
refuse on the chip (unaligned tiles, too much VMEM, a program that does not
fit HBM). The topology is described inside a fixture, never at import: only
one process may load the TPU library, and only the worker given this file
should. Keep every such compile in this one file.
"""
import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ssd_chunk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what the compiler reports as a v5e's usable HBM
V5E_HBM = 15.75 * 2**30


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip, so the cache is off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smoke():
    """chip_smoke.py's module: its config is what these compiles guard."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _assert_fits(compiled):
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need < V5E_HBM, f"{need / 2**30:.2f} GiB of {V5E_HBM / 2**30}"


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kernel", ["chunk_states", "chunk_output"])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_ssd_intra_chunk_compiles_at_mamba2_widths(one_chip, kernel,
                                                   direction):
    """The SSD chunk kernels, and their backward kernels, at the mamba2
    cell's widths: 48 heads of P=64 sharing one B and C of N=128, chunk
    256, 8 rows of 4096; B and C per row, never broadcast per head."""
    R, S, H, P, N, L = 8, 4096, 48, 64, 128, 256
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    rows, x = sd((R, H, S), jnp.float32), sd((R, S, H * P), jnp.float32)
    bc, s = sd((R, S, N), jnp.float32), sd((S // L, R, H * P, N), jnp.float32)
    if kernel == "chunk_states":
        fn, args, out = ssd_chunk.chunk_states, (rows, rows, x, bc), s
    else:
        fn, args, out = ssd_chunk.chunk_output, (rows, rows, x, bc, bc, s), x
    fn = functools.partial(fn, chunk=L)
    if direction == "forward":
        _compile(fn, *args)
    else:
        _compile(lambda d, *a: jax.vjp(fn, *a)[1](d), out, *args)


def test_ssd_intra_chunk_refuses_unaligned_chunk():
    a = jnp.zeros((1, 64, 2))
    x = jnp.zeros((1, 64, 2, 64))
    b = jnp.zeros((1, 64, 8))
    with pytest.raises(ValueError, match="multiples of 128"):
        ssd_chunk.ssd_intra_chunk(a, x, b, b, chunk=64, interpret=False)


def test_smoke_train_step_fits_one_v5e(smoke, topo):
    """The one-chip step of chip_smoke.py's config (phi3-mini-3.8b at
    published widths, depth cut) compiles for a v5e and fits its HBM.
    Batch 1 keeps the compile near 20 s; the step's memory is dominated by
    the model state and the packed exchange, not by the batch."""
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.train import build_train_step, make_batch_defs
    _, cfg = smoke.smoke_config()
    mesh = make_host_mesh((1, 1), ("data", "model"),
                          devices=topo.devices[:1])
    build = build_train_step(cfg, smoke.elastic_config(), mesh, n_pods=1,
                             per_pod_batch=1, seq=smoke.SEQ)
    compiled = build.step.lower(
        build.abstract_state,
        make_batch_defs(cfg, 1, 1, smoke.SEQ)).compile()
    _assert_fits(compiled)


def test_mamba2_cell_step_fits_one_v5e(topo):
    """The step of the benchmark's ``mamba2_b8s4096_1chip`` cell (14 of
    mamba2-780m's 48 layers at published widths, one worker, batch 8 x
    4096, the chunked scan at chunk 256) compiles for a v5e and fits its
    HBM."""
    from bench import run, spec
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.train import build_train_step, make_batch_defs
    bench = spec.benchmark()
    w = spec.workload(bench, "mamba2_b8s4096_1chip")
    cfg = run.program_config(spec.config(bench, w["config"]))
    t = spec.traffic(w["traffic"])
    B, S = t["batch_per_worker"], t["seq"]
    mesh = make_host_mesh((1, 1), ("data", "model"),
                          devices=topo.devices[:1])
    build = build_train_step(cfg, run.elastic_config(t), mesh, n_pods=1,
                             per_pod_batch=B, seq=S)
    compiled = build.step.lower(build.abstract_state,
                                make_batch_defs(cfg, 1, B, S)).compile()
    _assert_fits(compiled)
    # its scan runs the two chunk kernels, forward (twice, with the remat)
    # and backward, under the scope that records them on the device trace
    kernel_ops = _kernel_op_names(compiled.as_text())
    assert len(kernel_ops) == 6, kernel_ops
    for op_name in kernel_ops:
        assert "/ssm.scan/ssm.scan.kernel/" in op_name, op_name
    # no more memory than the jnp scan's step took (14.328 GiB)
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need <= 14.328 * 2**30, need / 2**30


def _kernel_op_names(text: str) -> list:
    """op_name of every Mosaic kernel call in a compiled module's text (an
    instruction's frontend attributes may span lines: joined first)."""
    text = re.sub(r"\n(?=[\"}])", " ", text)
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines()
            if "custom-call(" in line and "tpu_custom_call" in line]


def test_four_pod_step_runs_flash_kernel_per_device(smoke, topo, monkeypatch):
    """The 4-pod phi3 step (chip_smoke.py's config, batch 1 per pod) for a
    described v5e:2x2: its attention runs the Pallas kernel under the
    ``attention.flash`` scope, inside the per-pod vmap (where the benchmark's
    layer rule counts fwd/bwd), with no collective beyond those of the same
    step on the blocked path, and the step fits a v5e."""
    import collections

    from repro.launch.mesh import make_host_mesh
    from repro.models import attention
    from repro.runtime.train import build_train_step, make_batch_defs

    _, cfg = smoke.smoke_config()
    mesh = make_host_mesh((4, 1, 1), ("pod", "data", "model"),
                          devices=topo.devices)

    def compile_step():
        build = build_train_step(cfg, smoke.elastic_config(), mesh, n_pods=4,
                                 per_pod_batch=1, seq=smoke.SEQ)
        return build.step.lower(
            build.abstract_state,
            make_batch_defs(cfg, 4, 1, smoke.SEQ)).compile()

    def collectives(text):
        return collections.Counter(re.findall(
            r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", text))

    compiled = compile_step()
    text = compiled.as_text()
    kernel_ops = _kernel_op_names(text)
    assert kernel_ops
    for op_name in kernel_ops:
        assert "/attention.flash/" in op_name, op_name
        assert op_name.startswith("jit(sync_easgd_step)/vmap("), op_name
    _assert_fits(compiled)

    monkeypatch.setattr(attention, "_kernel_placement", lambda *a: None)
    blocked = compile_step().as_text()
    assert "tpu_custom_call" not in blocked
    assert not collectives(text) - collectives(blocked)
