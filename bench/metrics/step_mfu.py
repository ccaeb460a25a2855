"""The whole step's share of the chip's bf16 peak while the device runs
it, in percent: model FLOPs of the steps traced, per chip, over the peak
times the device's busy time in the trace (the union of its ops, mean over
the cell's devices). Device time alone: with ``device_idle_share`` it makes
up the end-to-end ``mfu``, and it bounds the step's kernels from above."""


def read(ctx):
    t = ctx["trace"]
    flops = ctx["flops_per_step_per_chip"] * t["steps"]
    return 100.0 * flops / (ctx["peaks"]["bf16_flops_per_s"] * t["busy_s"])
