"""95th percentile, over the requests due in the window, of the wait in
the scheduler's queue: the engine's ``t_prefill_start`` minus the
request's due time (its ``arrival``, on the harness's clock)."""
import numpy as np


def read(ctx):
    m = ctx.driver.engine_metrics
    waits = [m[r.item.idx].t_prefill_start - r.due
             for r in ctx.driver.window_requests()
             if r.item.idx in m and m[r.item.idx].t_prefill_start is not None]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
