"""Mean host-clock wall time of the window's ``Engine.step()`` calls that
dispatched the mixed program.  Each ends in the sampler's copy to the
host, so it spans the device step."""


def read(ctx):
    lo, hi = ctx.t0, ctx.t0 + ctx.window_s
    d = [s.t1 - s.t0 for s in ctx.driver.steps
         if s.dispatched and lo <= s.t0 < hi]
    return sum(d) / len(d) * 1e3 if d else None
