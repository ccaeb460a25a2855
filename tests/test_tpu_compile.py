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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

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


def _smoke_param_elems(smoke) -> int:
    from repro.models import transformer as tfm
    from repro.models.common import abstract_params
    _, cfg = smoke.smoke_config()
    return sum(leaf.size for leaf in jax.tree_util.tree_leaves(
        abstract_params(tfm.model_defs(cfg), cfg.param_dtype)))


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("aligned", [True, False])
def test_elastic_update_compiles_at_smoke_packed_size(smoke, one_chip,
                                                      aligned):
    from repro.core.packing import ELASTIC_UPDATE_BLOCK
    n = _smoke_param_elems(smoke)
    if aligned:       # the packer's layout
        n = -(-n // ELASTIC_UPDATE_BLOCK) * ELASTIC_UPDATE_BLOCK
    buf = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    fn = functools.partial(ops.elastic_update, eta=0.05, rho=4.5, mu=0.9,
                           n_workers=4, interpret=False)
    _compile(fn, *[buf] * 5)


def test_fused_cross_entropy_compiles_at_phi3_vocab(one_chip):
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(functools.partial(ops.fused_cross_entropy, interpret=False),
             sd((8192, 3072), jnp.bfloat16), sd((3072, 32064), jnp.bfloat16),
             sd((8192,), jnp.int32))


def test_ssd_intra_chunk_compiles_at_mamba2_widths(one_chip):
    # mamba2: 48 heads of P=64, state N=128, chunk 256; batch 2 x 4096
    BH, S, P, N = 2 * 48, 4096, 64, 128
    sd = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    _compile(functools.partial(ops.ssd_intra_chunk, chunk=256,
                               interpret=False),
             sd((BH, S), jnp.float32), sd((BH, S, P), jnp.bfloat16),
             sd((BH, S, N), jnp.bfloat16), sd((BH, S, N), jnp.bfloat16))


def test_ssd_intra_chunk_refuses_unaligned_chunk():
    a = jnp.zeros((1, 64))
    x = jnp.zeros((1, 64, 8))
    with pytest.raises(ValueError, match="multiples of 128"):
        ops.ssd_intra_chunk(a, x, x, x, chunk=64, interpret=False)


def test_flash_attention_compiles_at_phi3_widths(one_chip):
    qkv = jax.ShapeDtypeStruct((1, 4096, 32, 96), jnp.bfloat16,
                               sharding=one_chip)
    _compile(functools.partial(ops.flash_attention, interpret=False),
             qkv, qkv, qkv)


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
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert need < V5E_HBM, f"{need / 2**30:.2f} GiB of {V5E_HBM / 2**30}"
