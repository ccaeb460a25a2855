"""Device self time per step of the ops under the named scope ``ssm.scan``
(``models/ssm._ssd_chunked``): the chunked SSD scan, its intra-chunk
products and the state passed between chunks, forward, backward and
remat. Mean over the cell's devices; ``None`` where the step has no such
scope."""


def read(ctx):
    t = ctx["trace"]
    s = t.get("scope_s", {}).get("ssm.scan", 0.0)
    return 1e3 * s / t["steps"] if s > 0 else None
