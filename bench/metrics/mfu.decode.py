"""Model-step layer: model FLOPs of decode-only iterations over their
summed host time, as a share of the chip's bf16 peak."""
from harness import flops
from harness.records import decode_only, window_steps


def read(ctx):
    steps = decode_only(window_steps(ctx))
    secs = sum(s["t1"] - s["t0"] for s in steps)
    if not steps or secs <= 0:
        return None
    work = sum(flops.row_flops(ctx.cfg, c - 1, 1)
               for s in steps for c in s["decode"])
    return 100.0 * work / secs / ctx.peak["bf16_flops_per_s"]
