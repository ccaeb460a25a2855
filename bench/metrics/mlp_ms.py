"""Device self time per step of the ops under the named scope ``mlp``
(``models/ffn.ffn_block``): the SwiGLU, forward, backward and remat. Mean
over the cell's devices; ``None`` where the step has no such scope."""


def read(ctx):
    t = ctx["trace"]
    s = t.get("scope_s", {}).get("mlp", 0.0)
    return 1e3 * s / t["steps"] if s > 0 else None
