"""Share of its roofline that the mixed program reaches in the traced
stretch: the least time of each call the harness saw dispatched there
(``bench/harness/costs.py``: useful FLOPs over peak, or least bytes over
HBM bandwidth, whichever is longer), summed, over the device time of the
``mixed_fn`` program runs in the trace."""
from harness import costs, trace


def read(ctx):
    if not ctx.trace or not ctx.calls_in_trace:
        return None
    dev_ns, runs = trace.program_time(ctx.trace, "mixed_fn")
    if dev_ns <= 0 or runs == 0:
        return None
    ideal = 0.0
    for c in ctx.calls_in_trace:
        t, _ = costs.ideal_seconds(ctx.call_flops(c), ctx.call_bytes(c), ctx.peaks)
        ideal += t
    # per-call means: a run cut by the trace's edges is clipped on both sides
    return 100.0 * (ideal / len(ctx.calls_in_trace)) / (dev_ns * 1e-9 / runs)
