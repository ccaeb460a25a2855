"""Host time per step spent getting the batch: ``pipe.next()`` and its
``device_put``, from the harness's ``bench.input`` spans."""


def read(ctx):
    return 1e3 * ctx["trace"]["host_s"]["bench.input"] / ctx["trace"]["steps"]
