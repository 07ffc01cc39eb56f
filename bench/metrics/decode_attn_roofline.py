"""Kernels layer: the paged decode attention kernel
(``kernels/paged_attention.py``). The least time the chip needs for the
decode rows' attention over their live context in the traced slice, over
that kernel's device time in the trace."""
from harness import flops, kernels
from harness import trace as tr
from harness.records import slice_steps


def read(ctx):
    calls = [s["decode"] for s in slice_steps(ctx) if s["decode"]]
    if not calls:
        return None
    best = sum(flops.least_time(*flops.decode_attn_cost(ctx.cfg, call),
                                ctx.peak) for call in calls)
    secs = tr.matching_seconds(ctx.trace, kernels.DECODE_ATTN)
    return 100.0 * best / secs if secs > 0 else None
