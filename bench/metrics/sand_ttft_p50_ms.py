"""Scheduler layer, as the client sees it: median wall-clock
milliseconds from a text (sand) request's scheduled send to its first
token, over the requests sent in the window; one never served counts
until the drain ended. A median of some two dozen requests whose TTFTs
fall into two groups (sent to an idle engine, or behind a step in
flight), so it swings between them from run to run and is not bounded
end to end."""
from harness.stats import percentile


def read(ctx):
    rec, drain_end = ctx.rec, ctx.phase["drain_end"]
    ttft = [(rec.stamps[rid][0] if rec.stamps[rid] else drain_end) - sched
            for rid, (sched, _sent) in rec.sends.items()
            if rec.specs[rid].modality == "text"]
    return percentile(ttft, 50) * 1e3 if ttft else None
