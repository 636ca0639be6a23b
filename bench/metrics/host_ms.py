"""Host time of a step: the mean, over the window's ``engine.step`` spans
that dispatched a program, of each span's length less its ``engine.sync``
spans, in which the host waits on the device.  Read from the engine's own
span ring (``EngineMetrics.spans``); an engine without one reports
nothing."""


def read(ctx):
    spans = getattr(ctx.driver.engine.metrics, "spans", None)
    if spans is None:
        return None
    lo, hi = ctx.t0, ctx.t0 + ctx.window_s
    steps, sync = {}, {}
    for s in spans:
        if s.name == "engine.sync":
            sync[s.step] = sync.get(s.step, 0.0) + (s.t1 - s.t0)
        elif (s.name == "engine.step" and lo <= s.t0 < hi and s.attrs
              and s.attrs["kind"] not in ("idle", "preempt")):
            steps[s.step] = s.t1 - s.t0
    if not steps:
        return None
    host = sum(d - sync.get(step, 0.0) for step, d in steps.items())
    return host / len(steps) * 1e3
