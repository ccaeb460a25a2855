"""Host time per step that ``ShardedPipeline.next()`` spent blocked on an
empty prefetch queue: the program's ``repro.input.wait`` spans, 0 when the
queue never ran dry. ``None`` where the trace holds no
``repro.input.produce`` span, so the program records no input spans."""


def read(ctx):
    t = ctx["trace"]
    host = t.get("program_host_s", {})
    if "repro.input.produce" not in host:
        return None
    return 1e3 * host.get("repro.input.wait", 0.0) / t["steps"]
