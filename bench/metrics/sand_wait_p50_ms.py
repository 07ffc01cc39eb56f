"""Scheduler layer: median wall-clock milliseconds from a text (sand)
request's scheduled send to the start of the first step that runs a
prefill chunk of it, over the requests sent in the window. One never
scheduled counts as waiting until the drain ended."""
from harness.stats import percentile


def read(ctx):
    rec, drain_end = ctx.rec, ctx.phase["drain_end"]
    waits = [rec.first_chunk.get(rid, drain_end) - sched
             for rid, (sched, _sent) in rec.sends.items()
             if rec.specs[rid].modality == "text"]
    return percentile(waits, 50) * 1e3 if waits else None
