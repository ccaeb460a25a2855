"""The update's share of its HBM roofline, in percent: the least bytes the
update must move, over the chip's HBM bandwidth, over ``update_ms``.

The least bytes, reckoned from the state's shapes on one device: the
update reads each pod-local element of the weights W, the momentum V, the
gradient G (at W's dtype) and the center C, and with more than one worker
the exchanged mean; it writes W, V and C. So 5 reads and 3 writes per
element with an exchange, 4 and 3 without one."""


def floor_bytes(state_bytes: dict, workers: int) -> float:
    w, v, c = (state_bytes["params"], state_bytes["momentum"],
               state_bytes["center"])
    mean = c if workers > 1 else 0.0
    return 2 * w + 2 * v + w + 2 * c + mean


def read(ctx):
    t = ctx["trace"]
    s = t["layer_s"].get("update", 0.0) / t["steps"]
    if s <= 0:
        return None
    least = floor_bytes(ctx["state_bytes"], ctx["workers"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / s
