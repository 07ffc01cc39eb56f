"""Kernels layer: the paged prefill attention kernel
(``kernels/paged_prefill_attention.py``). The least time the chip needs for
the causal attention of the live chunks in the traced slice (served
chunks and admission-profiling prefills; the larger of FLOPs over peak
and least bytes over bandwidth, from live lengths), over that kernel's
device time in the trace."""
from harness import flops, kernels
from harness import trace as tr
from harness.records import slice_admissions, slice_steps


def read(ctx):
    calls = [call for s in slice_steps(ctx) for call in s["prefill"]]
    calls += [call for a in slice_admissions(ctx) for call in a["calls"]]
    if not calls:
        return None
    best = sum(flops.least_time(*flops.prefill_attn_cost(ctx.cfg, call),
                                ctx.peak) for call in calls)
    secs = tr.matching_seconds(ctx.trace, kernels.PREFILL_ATTN)
    return 100.0 * best / secs if secs > 0 else None
