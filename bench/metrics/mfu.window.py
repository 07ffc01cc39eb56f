"""Model-step layer: useful model FLOPs the served steps completed in the
window (admission-time profiling prefills left out), over the window's
seconds, as a share of the chip's bf16 peak."""
from harness import flops
from harness.records import window_steps


def read(ctx):
    steps = window_steps(ctx)
    if not steps:
        return None
    work = sum(flops.row_flops(ctx.cfg, start, n)
               for s in steps for call in s["prefill"] for start, n in call)
    work += sum(flops.row_flops(ctx.cfg, c - 1, 1)
                for s in steps for c in s["decode"])
    return (100.0 * work / ctx.phase["window_end"]
            / ctx.peak["bf16_flops_per_s"])
