"""Selections over a run's records that the per-layer readers share."""
from __future__ import annotations


def window_steps(ctx) -> list[dict]:
    """Served steps that ended inside the window."""
    end = ctx.phase["window_end"]
    return [s for s in ctx.rec.steps if s["t1"] <= end]


def slice_steps(ctx) -> list[dict]:
    """Served steps wholly inside the traced slice (its device work is in
    the trace: each step returns only after its outputs reach the host)."""
    lo, hi = ctx.slice
    return [s for s in ctx.rec.steps if s["t0"] >= lo and s["t1"] <= hi]


def slice_admissions(ctx) -> list[dict]:
    lo, hi = ctx.slice
    return [a for a in ctx.rec.admissions if a["t0"] >= lo and a["t1"] <= hi]


def decode_only(steps) -> list[dict]:
    return [s for s in steps
            if s["decode"] and not s["prefill"] and not s["encode"]]
