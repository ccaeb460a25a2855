"""Device self time per step of the packed exchange's flat copies: the ops
under the named scopes ``elastic.pack`` and ``elastic.unpack``
(``core/elastic._exchange_packed``), a part of ``update_ms``. Mean over the
cell's devices; ``None`` where the step has neither scope."""


def read(ctx):
    t = ctx["trace"]
    scope_s = t.get("scope_s", {})
    s = scope_s.get("elastic.pack", 0.0) + scope_s.get("elastic.unpack", 0.0)
    return 1e3 * s / t["steps"] if s > 0 else None
