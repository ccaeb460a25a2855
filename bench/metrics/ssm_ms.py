"""Device self time per step of the ops under the named scope ``ssm``
(``models/ssm.ssm_block``) and outside ``ssm.scan``: the input and output
projections, the causal convolution, the gating and the gated RMSNorm,
forward, backward and remat. Mean over the cell's devices; ``None`` where
the step has no such scope."""


def read(ctx):
    t = ctx["trace"]
    s = t.get("scope_s", {}).get("ssm", 0.0)
    return 1e3 * s / t["steps"] if s > 0 else None
