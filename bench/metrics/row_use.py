"""Share of the mixed program's rows that carry live tokens: over the
window's mixed ``engine.dispatch`` spans, the prefill tokens of the
composed chunks plus the active decode rows (``rows_live``), over the rows
of the program's static shape (``rows_program``).  Read from the engine's
own span ring (``EngineMetrics.spans``); an engine without one reports
nothing."""


def read(ctx):
    spans = getattr(ctx.driver.engine.metrics, "spans", None)
    if spans is None:
        return None
    lo, hi = ctx.t0, ctx.t0 + ctx.window_s
    live = program = 0
    for s in spans:
        if (s.name == "engine.dispatch" and lo <= s.t0 < hi and s.attrs
                and s.attrs["program"] == "mixed"):
            live += s.attrs["rows_live"]
            program += s.attrs["rows_program"]
    return 100.0 * live / program if program else None
