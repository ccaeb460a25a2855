"""Attention paths and the SSD chunk kernels against pure-jnp oracles
(the Pallas kernels with interpret=True on CPU), with shape/dtype sweeps."""
import functools
import warnings

warnings.filterwarnings("ignore")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, ssd_chunk
from repro.models.attention import blocked_attention


@pytest.mark.parametrize(
    "B,S,H,KVH,D,causal,window,dtype,block",
    [
        (2, 64, 4, 2, 32, True, 0, jnp.float32, 32),
        (1, 100, 2, 2, 16, True, 9, jnp.float32, 32),   # padded to blocks
        (2, 128, 4, 1, 64, False, 0, jnp.bfloat16, 32),
        (1, 256, 8, 4, 128, True, 64, jnp.float32, 32),
        (1, 96, 4, 4, 8, True, 0, jnp.bfloat16, 32),
        (2, 64, 4, 4, 16, True, 0, jnp.float32, 16),
    ],
)
def test_blocked_attention_matches_dense(B, S, H, KVH, D, causal, window,
                                         dtype, block):
    """The blocked online-softmax path (every CPU run, and every shape the
    splash kernel refuses) against the dense (S×S) oracle, with kv heads
    expanded for the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, D), dtype)
    out = blocked_attention(q, k, v, causal=causal, window=window,
                            q_block=block, kv_block=block)

    def heads_first(t):
        t = jnp.repeat(t, H // t.shape[2], axis=2)
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    want = ref.flash_attention_dense_ref(heads_first(q), heads_first(k),
                                         heads_first(v), causal=causal,
                                         window=window)
    want = want.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def _ssd_inputs(R, S, H, P, N, dt_shift=0.0, seed=3):
    """Log-decay a (R, S, H), Δt·x (R, S, H, P) and one group's B, C
    (R, S, N); ``dt_shift`` raises the step sizes."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    a = -jax.nn.softplus(jax.random.normal(ks[0], (R, S, H)) + dt_shift)
    x = jax.random.normal(ks[1], (R, S, H, P))
    b = jax.random.normal(ks[2], (R, S, N))
    c = jax.random.normal(ks[3], (R, S, N))
    return a, x, b, c


def _rel_gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("R,S,H,P,N,L", [
    (2, 128, 4, 16, 8, 32),       # four chunks; one block, under a lane tile
    (1, 256, 16, 64, 32, 64),     # mamba2's head_dim: two blocks of 8 heads
    (1, 128, 2, 128, 16, 64),     # one block of 2 heads, a lane tile each
])
def test_ssd_intra_kernel(R, S, H, P, N, L):
    """The output kernel with no entering state (the intra-chunk term) and
    its backward kernel against the plain oracle, output and gradients in
    a, x̄, B and C, with every head sharing one B and C; float32 products,
    so only the order of sums differs."""
    a, x, b, c = _ssd_inputs(R, S, H, P, N)
    dy = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    kernel = jax.jit(functools.partial(ssd_chunk.ssd_intra_chunk, chunk=L,
                                       interpret=True))
    oracle = lambda *t: ref.ssd_intra_ref(*t, chunk=L)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(kernel, a, x, b, c)
        want, vjp_ref = jax.vjp(oracle, a, x, b, c)
        grads, grads_ref = vjp(dy), vjp_ref(dy)
    for name, u, w in zip(("y", "da", "dx", "db", "dc"), (got, *grads),
                          (want, *grads_ref)):
        assert np.all(np.isfinite(np.asarray(u))), name
        assert _rel_gap(u, w) < 1e-5, (name, _rel_gap(u, w))


def test_ssd_intra_kernel_default_precision_is_one_bf16_pass():
    """Under the default precision the kernel's products round their
    operands to bfloat16 once, as XLA's default float32 einsum on a TPU
    does: closer to the float32 oracle than bfloat16's 2^-8, and not exact."""
    a, x, b, c = _ssd_inputs(1, 128, 4, 32, 16)
    got = jax.jit(functools.partial(ssd_chunk.ssd_intra_chunk, chunk=64,
                                    interpret=True))(a, x, b, c)
    with jax.default_matmul_precision("highest"):
        want = ref.ssd_intra_ref(a, x, b, c, chunk=64)
    assert 1e-5 < _rel_gap(got, want) < 2 ** -8


@pytest.mark.parametrize(
    "B,S,H,KVH,causal,window,dtype",
    [
        (2, 96, 4, 2, True, 11, jnp.float32),     # window, GQA 2:1
        (2, 96, 4, 4, True, 0, jnp.float32),      # no window, H == KVH
        (2, 96, 4, 2, False, 0, jnp.float32),     # non-causal
        (1, 100, 8, 2, True, 0, jnp.float32),     # GQA 4:1, padded to blocks
        (2, 96, 4, 2, True, 11, jnp.bfloat16),
    ],
)
def test_flash_train_custom_vjp_matches_autodiff(B, S, H, KVH, causal, window,
                                                 dtype):
    """The blocked custom VJP's (dq, dk, dv) against autodiff through the
    blocked forward: float32 to 1e-4, bfloat16 within bfloat16 rounding of
    each gradient's scale."""
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    from repro.models.attention import flash_attention_train
    q = jax.random.normal(ks[0], (B, S, H, 16), dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, 16), dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, 16), dtype)
    dout = jax.random.normal(ks[3], (B, S, H, 16), dtype)
    kw = dict(causal=causal, window=window, q_block=32, kv_block=16)
    f1 = lambda q, k, v: jnp.sum(
        flash_attention_train(q, k, v, **kw).astype(jnp.float32) * dout)
    f2 = lambda q, k, v: jnp.sum(
        blocked_attention(q, k, v, **kw).astype(jnp.float32) * dout)
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g1, g2):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.bfloat16:
            np.testing.assert_allclose(a, b, rtol=2e-2,
                                       atol=2e-2 * np.abs(b).max(),
                                       err_msg=name)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4,
                                       err_msg=name)


def _kernel_vs_blocked(q, k, v, dout, window, q_block, kv_block):
    """(out, dq, dk, dv) of the Pallas kernel path (interpreted) and of the
    blocked custom-VJP path, as float32 numpy."""
    from repro.models import attention as A

    kw = dict(window=window, q_block=q_block, kv_block=kv_block)
    placement = A._kernel_placement(q, k, v, True)
    assert placement is not None

    def kernel(q, k, v):
        return A._flash_kernel(q, k, v, placement=placement,
                               interpret=True, **kw)

    def blocked(q, k, v):
        return A._flash_blocked(q, k, v, causal=True, **kw)

    def run(f):
        out, vjp = jax.vjp(f, q, k, v)
        return [np.asarray(x, np.float32) for x in (out, *vjp(dout))]

    return run(kernel), run(blocked)


@pytest.mark.parametrize(
    "B,S,H,KVH,D,window,q_block,kv_block",
    [
        (2, 256, 4, 4, 96, 0, 128, 128),     # phi3's head_dim, H == KVH
        (2, 256, 4, 2, 64, 0, 128, 256),     # GQA: 2 query heads per kv head
        (1, 200, 4, 2, 96, 0, 512, 1024),    # padded up to one 256 tile
        (1, 256, 4, 2, 64, 37, 128, 128),    # odd sliding window
        (1, 300, 2, 1, 64, 0, 96, 48),       # blocks the kernel refuses
    ],
)
def test_flash_kernel_matches_blocked_path(B, S, H, KVH, D, window, q_block,
                                           kv_block):
    """The training path's Pallas kernel (what a TPU lowering runs) against
    the blocked path it replaces: output and (dq, dk, dv), bfloat16 inputs,
    within bfloat16 rounding of each array's scale."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KVH, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KVH, D), jnp.bfloat16)
    dout = jax.random.normal(ks[3], (B, S, H, D), jnp.bfloat16)
    got, want = _kernel_vs_blocked(q, k, v, dout, window, q_block, kv_block)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        scale = np.abs(b).max()
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("causal,Skv,D,Dv", [
    (False, 256, 64, 64),     # full attention
    (True, 128, 64, 64),      # cross-length
    (True, 256, 192, 128),    # MLA: Dv != D
    (True, 256, 512, 512),    # past MAX_HEAD_DIM
])
def test_flash_kernel_refuses_shapes(causal, Skv, D, Dv):
    """Shapes the kernel refuses keep the blocked path on every platform."""
    from repro.models import attention as A

    sd = jax.ShapeDtypeStruct
    q = sd((1, 256, 4, D), jnp.bfloat16)
    k = sd((1, Skv, 4, D), jnp.bfloat16)
    v = sd((1, Skv, 4, Dv), jnp.bfloat16)
    assert A._kernel_placement(q, k, v, causal) is None


def _strip_hlo(text: str) -> list:
    """A compiled module's instructions without metadata, source locations
    and instruction numbering."""
    import re
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if re.match(r"(%|ENTRY)", l))
    out = []
    for line in lines[start:]:
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        out.append(re.sub(r"(%[\w\-]+)\.\d+", r"\1", line))
    return out


def test_flash_train_cpu_lowering_is_the_blocked_path():
    """Lowered for the CPU, flash_attention_train holds no Mosaic custom
    call, and its compiled gradient is the blocked path's, op for op."""
    from repro.models import attention as A

    q = jax.ShapeDtypeStruct((2, 256, 4, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 256, 2, 64), jnp.bfloat16)
    kw = dict(causal=True, window=0, q_block=128, kv_block=128)

    def compiled(attn):
        loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile().as_text()

    train = compiled(lambda q, k, v: A.flash_attention_train(q, k, v, **kw))
    assert "tpu_custom_call" not in train
    assert _strip_hlo(train) == _strip_hlo(
        compiled(lambda q, k, v: A._flash_blocked(q, k, v, **kw)))


def test_flash_kernel_per_device_under_pod_vmap(subproc):
    """Under the train step's vmap over pods, the kernel runs per device in
    a shard_map (heads split over model), with no gather of q, k or v, and
    matches the blocked path."""
    out = subproc("""
        from functools import partial
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.models import attention as A, sctx
        from repro.runtime.sharding import activation_spec
        mesh = make_host_mesh((2, 1, 2), ("pod", "data", "model"))
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (2, 2, 256, 4, 64), jnp.bfloat16)
        k = jax.random.normal(ks[1], (2, 2, 256, 2, 64), jnp.bfloat16)
        v = jax.random.normal(ks[2], (2, 2, 256, 2, 64), jnp.bfloat16)
        do = jax.random.normal(ks[3], (2, 2, 256, 4, 64), jnp.bfloat16)
        kw = dict(window=0, q_block=128, kv_block=128)

        def kernel(q, k, v):
            placement = A._kernel_placement(q, k, v, True)
            assert placement[1][2] == "model", placement
            return A._flash_kernel(q, k, v, placement=placement,
                                   interpret=True, **kw)

        def step_of(attn):
            def per_pod(q, k, v, do):
                out, vjp = jax.vjp(attn, q, k, v)
                return (out, *vjp(do))

            @jax.jit
            def step(q, k, v, do):
                with sctx.use(mesh, activation_spec(None, mesh)):
                    return jax.vmap(per_pod, spmd_axis_name="pod")(q, k, v, do)
            return step

        step = step_of(kernel)
        text = step.lower(q, k, v, do).compile().as_text()
        assert "all-gather" not in text
        got = step(q, k, v, do)
        blocked = partial(A._flash_blocked, causal=True, **kw)
        want = step_of(blocked)(q, k, v, do)
        for a, b in zip(got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            scale = np.abs(b).max()
            np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2 * scale)
        print("OK")
    """, n_devices=4)
    assert "OK" in out


def _ssd_scan_inputs(Bsz, S, H, P, N, state, seed=1):
    """(xh, dt, A, B, C, state0) as the Mamba-2 mixer hands them to its
    scan: B and C bfloat16, one group shared by every head; step sizes
    whose sum over a chunk of 256 passes float32's exp range."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    xh = jax.random.normal(ks[0], (Bsz, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, H)) + 1.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (Bsz, S, N), jnp.bfloat16)
    Cm = jax.random.normal(ks[4], (Bsz, S, N), jnp.bfloat16)
    s0 = jax.random.normal(ks[5], (Bsz, H, P, N)) if state else None
    return xh, dt, A, Bm, Cm, s0


def _scan_value_and_grads(scan, inputs, chunk):
    """(y, final state, and the gradients of every input) of a random
    linear function of the scan's outputs, as float32 numpy."""
    xh, dt, A, Bm, Cm, s0 = inputs
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    dy = jax.random.normal(ks[0], xh.shape)
    ds = jax.random.normal(ks[1], xh.shape[:1] + (xh.shape[2], xh.shape[3],
                                                  Bm.shape[-1]))

    def loss(*args):
        y, s = scan(*args[:5], chunk, *args[5:])
        return jnp.sum(y * dy) + jnp.sum(s * ds), (y, s)

    args = (xh, dt, A, Bm, Cm) + ((s0,) if s0 is not None else ())
    (_, outs), grads = jax.value_and_grad(
        loss, argnums=tuple(range(len(args))), has_aux=True)(*args)
    return [np.asarray(t, np.float32) for t in (*outs, *grads)]


@pytest.mark.parametrize("Bsz,S,H,P,N,L,state", [
    (2, 128, 4, 16, 8, 32, False),    # four chunks, heads sharing B and C
    (2, 128, 4, 16, 8, 32, True),     # a state carried in (prefill)
    (1, 200, 16, 64, 32, 64, True),   # mamba2's head_dim; padded to 4 chunks
    (1, 512, 4, 16, 16, 256, False),  # the published chunk: exp range passed
])
def test_ssd_kernel_path_matches_jnp_path(Bsz, S, H, P, N, L, state):
    """The scan with its contractions over each chunk's positions in the
    Pallas kernels (what a TPU lowering runs; interpreted) against
    ``_ssd_chunked``, its oracle: y, the final state and the gradients of
    x, dt, A, B, C (and state0)."""
    from repro.models import ssm

    inputs = _ssd_scan_inputs(Bsz, S, H, P, N, state)
    kernel = functools.partial(ssm._ssd_chunked_kernel,
                               placement=(None, None), interpret=True)
    names = ["y", "state", "dx", "ddt", "dA", "dB", "dC", "dstate0"]
    with jax.default_matmul_precision("highest"):
        got = _scan_value_and_grads(kernel, inputs, L)
        want = _scan_value_and_grads(ssm._ssd_chunked, inputs, L)
    for name, u, w in zip(names, got, want):
        assert np.all(np.isfinite(u)), name
        # A's gradient sums over every position, the widest of the float32
        # ones; B's and C's are bfloat16, as B and C are: a rounding apart
        tol = 2 ** -7 if name in ("dB", "dC") else 1e-4
        assert _rel_gap(u, w) < tol, (name, _rel_gap(u, w))


@pytest.mark.parametrize("chunk,H,P,why", [
    (16, 48, 64, "the tiny config's chunk: not a multiple of 128 lanes"),
    (200, 48, 64, "an unaligned chunk"),
    (256, 48, 12, "a head_dim that is not whole sublane tiles"),
    (256, 3, 64, "heads that leave half a lane tile"),
])
def test_ssd_kernel_refuses_shapes(chunk, H, P, why):
    """Shapes the kernels refuse keep ``_ssd_chunked`` on every platform."""
    from repro.models import ssm

    sd = jax.ShapeDtypeStruct
    xh, bm = sd((2, 1024, H, P), jnp.float32), sd((2, 1024, 128), jnp.bfloat16)
    assert ssm._kernel_placement(xh, bm, chunk) is None, why
    assert ssm._kernel_placement(sd((2, 1024, 48, 64), jnp.float32), bm,
                                 256) == (None, None)


def test_ssd_scan_cpu_lowering_is_the_jnp_path():
    """Lowered for the CPU, ``ssd_scan`` holds no Mosaic custom call, and
    its compiled gradient is ``_ssd_chunked``'s, op for op."""
    from repro.models import ssm

    sd = jax.ShapeDtypeStruct
    args = (sd((2, 512, 4, 64), jnp.float32), sd((2, 512, 4), jnp.float32),
            sd((4,), jnp.float32), sd((2, 512, 32), jnp.bfloat16),
            sd((2, 512, 32), jnp.bfloat16))

    def compiled(scan):
        def loss(*a):
            y, s = scan(*a, 256)
            return jnp.sum(y ** 2) + jnp.sum(s ** 2)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()

    text = compiled(ssm.ssd_scan)
    assert "tpu_custom_call" not in text
    assert _strip_hlo(text) == _strip_hlo(compiled(ssm._ssd_chunked))


def test_ssd_kernel_per_device_under_pod_vmap(subproc):
    """Under the train step's vmap over pods, the kernels run per device in
    a shard_map (batch over data, heads over model), with no gather of x,
    and their B and C gradients sum over the head shards: they match the
    jnp path."""
    out = subproc("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.models import sctx, ssm
        from repro.runtime.sharding import activation_spec
        mesh = make_host_mesh((2, 1, 2), ("pod", "data", "model"))
        ks = jax.random.split(jax.random.PRNGKey(0), 7)
        xh = jax.random.normal(ks[0], (2, 2, 256, 16, 64))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 2, 256, 16)))
        A = -jnp.exp(0.5 * jax.random.normal(ks[2], (2, 16)))
        Bm = jax.random.normal(ks[3], (2, 2, 256, 32), jnp.bfloat16)
        Cm = jax.random.normal(ks[4], (2, 2, 256, 32), jnp.bfloat16)
        dy = jax.random.normal(ks[5], xh.shape)
        ds = jax.random.normal(ks[6], (2, 2, 16, 64, 32))

        def kernel(xh, dt, A, Bm, Cm, chunk):
            placement = ssm._kernel_placement(xh, Bm, chunk)
            assert placement[1]["x"][2] == "model", placement
            return ssm._ssd_chunked_kernel(xh, dt, A, Bm, Cm, chunk,
                                           placement=placement,
                                           interpret=True)

        def step_of(scan):
            def per_pod(xh, dt, A, Bm, Cm, dy, ds):
                def loss(*a):
                    y, s = scan(*a, 128)
                    return jnp.sum(y * dy) + jnp.sum(s * ds), (y, s)
                (_, outs), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
                        xh, dt, A, Bm, Cm)
                return (*outs, *grads)

            @jax.jit
            def step(*args):
                with sctx.use(mesh, activation_spec(None, mesh)):
                    return jax.vmap(per_pod, spmd_axis_name="pod")(*args)
            return step

        args = (xh, dt, A, Bm, Cm, dy, ds)
        step = step_of(kernel)
        text = step.lower(*args).compile().as_text()
        assert "all-gather" not in text
        with jax.default_matmul_precision("highest"):
            got = step_of(kernel)(*args)
            want = step_of(ssm._ssd_chunked)(*args)
        for a, b in zip(got, want):
            # dB and dC are bfloat16: one rounding of their float32 sums
            tol = 2 ** -7 if a.dtype == jnp.bfloat16 else 1e-4
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.abs(a - b).max() < tol * np.abs(b).max()
        print("OK")
    """, n_devices=4)
    assert "OK" in out
