"""Scheduler layer: mean decode rows per served iteration in the window."""
from harness.records import window_steps
from harness.stats import mean


def read(ctx):
    steps = window_steps(ctx)
    return mean(len(s["decode"]) for s in steps) if steps else None
