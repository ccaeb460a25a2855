"""Device idle time per step while the input producer runs: per device the
window less the union of its ops, intersected with the union of the
program's ``repro.input.produce`` spans (any thread); mean over the cell's
devices. ``None`` where the trace holds no such span."""


def read(ctx):
    t = ctx["trace"]
    if "repro.input.produce" not in t.get("program_host_s", {}):
        return None
    return 1e3 * t["idle_producing_s"] / t["steps"]
