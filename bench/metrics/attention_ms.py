"""Device self time per step of the ops under the named scope
``attention`` (``models/attention.attention_block``): the QKV and O
projections, RoPE and the flash core, forward, backward and remat. Mean
over the cell's devices; ``None`` where the step has no such scope."""


def read(ctx):
    t = ctx["trace"]
    s = t.get("scope_s", {}).get("attention", 0.0)
    return 1e3 * s / t["steps"] if s > 0 else None
