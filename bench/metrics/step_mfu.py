"""The whole model step's share of the chip's bf16 peak: useful model
FLOPs of the mixed calls dispatched in the window (prompt positions
recomputed after a preemption do not count), over the host-clock time
of the ``Engine.step()`` calls that dispatched them.  Unlike a share of
the whole window, it does not follow the offered load: a faster step
raises it."""


def read(ctx):
    lo, hi = ctx.t0, ctx.t0 + ctx.window_s
    busy = sum(s.t1 - s.t0 for s in ctx.driver.steps
               if s.dispatched and lo <= s.t0 < hi)
    if not ctx.calls_in_window or busy <= 0:
        return None
    flops = sum(ctx.call_flops(c, useful_only=True) for c in ctx.calls_in_window)
    return 100.0 * flops / (busy * ctx.peaks["bf16_flops_per_s"])
