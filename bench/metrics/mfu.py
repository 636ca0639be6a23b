"""Useful model FLOPs of the tokens the window computed (prompt and
output; prompt positions recomputed after a preemption do not count),
over the window's length times the chip's bf16 peak."""


def read(ctx):
    if not ctx.calls_in_window:
        return None
    flops = sum(ctx.call_flops(c, useful_only=True) for c in ctx.calls_in_window)
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
