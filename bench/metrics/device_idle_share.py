"""Device layer: share of the traced slice in which the engine had work
(the harness was not sleeping for the next arrival) but no operation ran
on the device: 1 - busy union / working time."""


def read(ctx):
    lo, hi = ctx.slice
    asleep = sum(max(0.0, min(b, hi) - max(a, lo))
                 for a, b in ctx.rec.waits)
    working = ctx.busy["window_s"] - asleep
    if working <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy["busy_s"] / working)
