"""Device time per step of the step's ops that are neither the update nor
a collective: the per-pod forward and backward pass."""


def read(ctx):
    t = ctx["trace"]
    s = t["layer_s"].get("fwd_bwd", 0.0)
    return 1e3 * s / t["steps"] if s > 0 else None
