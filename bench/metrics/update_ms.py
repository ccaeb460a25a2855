"""Device time per step of the elastic update: the step's ops outside the
per-pod value-and-grad that are not collectives (pack, update, unpack)."""


def read(ctx):
    t = ctx["trace"]
    s = t["layer_s"].get("update", 0.0)
    return 1e3 * s / t["steps"] if s > 0 else None
