"""Device time per step of the cross-worker collectives (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all), their
asynchronous start-to-done spans included."""


def read(ctx):
    t = ctx["trace"]
    return 1e3 * t["exchange_s"] / t["steps"] if t["exchange_s"] > 0 \
        else None
