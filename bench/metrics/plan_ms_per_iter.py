"""Scheduler layer: mean host milliseconds of ``Engine._plan`` per
iteration (encode plan, TCM ranking, admission to prefill), over the
window."""
from harness.stats import mean


def read(ctx):
    end = ctx.phase["window_end"]
    plans = [t1 - t0 for t0, t1 in ctx.rec.plans if t1 <= end]
    return mean(plans) * 1e3 if plans else None
