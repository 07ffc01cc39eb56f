"""Model-step layer: model FLOPs of the real tokens the served steps
processed (prefill chunks and decode rows, unpadded, with the LM head on
the one position each row emits), over the summed host time of those
steps (``run_iteration``), as a share of the chip's bf16 peak."""
from harness import flops
from harness.records import window_steps


def read(ctx):
    steps = window_steps(ctx)
    secs = sum(s["t1"] - s["t0"] for s in steps)
    if not steps or secs <= 0:
        return None
    work = sum(flops.row_flops(ctx.cfg, start, n)
               for s in steps for call in s["prefill"] for start, n in call)
    work += sum(flops.row_flops(ctx.cfg, c - 1, 1)
                for s in steps for c in s["decode"])
    return 100.0 * work / secs / ctx.peak["bf16_flops_per_s"]
