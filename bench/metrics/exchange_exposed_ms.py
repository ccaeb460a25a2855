"""The part of ``exchange_ms`` per step in which no other op runs on that
device: the communication the step does not hide."""


def read(ctx):
    t = ctx["trace"]
    if t["exchange_s"] <= 0:
        return None
    return 1e3 * t["exchange_exposed_s"] / t["steps"]
