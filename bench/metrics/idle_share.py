"""Share of the traced stretch in which no operation ran on the device:
1 - (union of the device-op intervals) / (traced window)."""


def read(ctx):
    if not ctx.trace or ctx.trace["idle_share"] is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
