"""Admission layer: host milliseconds per request ingested, spent in the
engine's SLO pricing (``ModelExecutor.isolated_e2e`` and the profiling
prefill ``isolated_run`` it runs), over the window."""


def read(ctx):
    end = ctx.phase["window_end"]
    calls = [a for a in ctx.rec.admissions if a["t1"] <= end]
    rids = {a["rid"] for a in calls}
    if not rids:
        return None
    return sum(a["t1"] - a["t0"] for a in calls) / len(rids) * 1e3
