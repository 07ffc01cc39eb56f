"""Model-step layer: mean host milliseconds of a served iteration
(``ModelExecutor.run_iteration``) that ran decode rows only, over the
window."""
from harness.records import decode_only, window_steps
from harness.stats import mean


def read(ctx):
    steps = decode_only(window_steps(ctx))
    return mean(s["t1"] - s["t0"] for s in steps) * 1e3 if steps else None
