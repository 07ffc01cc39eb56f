"""Profiler trace capture and reduction.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a small plain
dict (``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, dur_ns, stats]]}]}]}``) holding only the device planes and the
host thread that carries the harness's ``bench.*`` annotations. Every
reduction below works on that dict, so it can be checked on a small
recorded trace without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from pathlib import Path

from harness import kernels

HOST_PREFIX = "bench."
# the device line whose events are the executed HLO operations
OPS_LINE = "XLA Ops"


def load_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, "XLA Modules"):
                continue
            events = []
            for e in line.events:
                if not device and not e.name.startswith(HOST_PREFIX):
                    continue
                stats = {k: v for k, v in e.stats
                         if isinstance(v, (int, float, str))} if device else {}
                events.append([e.name, float(e.start_ns),
                               float(e.duration_ns), stats])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def save(trace: dict, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")]


def op_events(plane: dict) -> list[list]:
    return [e for line in plane["lines"] if line["name"] == OPS_LINE
            for e in line["events"]]


def host_spans(trace: dict) -> list[tuple[str, float, float]]:
    """(name without the prefix, start_ns, end_ns) of every annotation."""
    out = []
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for line in p["lines"]:
            for name, t, dur, _s in line["events"]:
                if name.startswith(HOST_PREFIX):
                    out.append((name[len(HOST_PREFIX):], t, t + dur))
    return out


def window(trace: dict) -> tuple[float, float]:
    """The traced window: the ``bench.traced`` annotation."""
    spans = [s for s in host_spans(trace) if s[0] == "traced"]
    if not spans:
        raise RuntimeError("trace has no bench.traced annotation")
    return spans[0][1], spans[0][2]


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy(trace: dict) -> dict:
    """Device busy seconds (union of op intervals, averaged over the
    device planes), the window's seconds, and each device's busy union."""
    lo, hi = window(trace)
    unions = []
    for p in device_planes(trace):
        unions.append(union(((t, t + d) for _n, t, d, _s in op_events(p)),
                            lo, hi))
    busy_ns = [sum(b - a for a, b in u) for u in unions]
    return {"busy_s": (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0,
            "window_s": (hi - lo) / 1e9, "unions": unions,
            "window_ns": (lo, hi)}


def idle_gaps(trace: dict) -> list[tuple[str, float]]:
    """Idle gaps of the first device, longest first, each named by the
    innermost harness annotation that covers its midpoint."""
    b = busy(trace)
    lo, hi = b["window_ns"]
    if not b["unions"]:
        return []
    spans = [s for s in host_spans(trace) if s[0] != "traced"]
    gaps, t = [], lo
    for a, e in b["unions"][0] + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, e)
    out = []
    for a, e in gaps:
        mid = (a + e) / 2
        cover = [s for s in spans if s[1] <= mid < s[2]]
        name = max(cover, key=lambda s: s[1])[0] if cover else "untraced"
        out.append((name, (e - a) / 1e9))
    return sorted(out, key=lambda g: -g[1])


_SHAPE = re.compile(r"[a-z0-9]+\[[^\]]*\]")
_OPCODE = re.compile(r"\s*([a-z][\w-]*)\(")
# ops that contain other ops' events (a loop, a call): their time is their
# body's, so they are left out of per-op sums
CONTAINERS = ("while", "call", "conditional")


def op_label(name: str) -> tuple[str, str]:
    """(short label, opcode) of a device op's HLO text
    ``%name = <type> opcode(...)``: the instruction name, its result shape
    (a tuple's first element) and its opcode."""
    head, _, rest = name.partition(" = ")
    i, depth = 0, 0
    while i < len(rest):        # skip the result type, tuples included
        ch = rest[i]
        depth += ch in "({[" and 1 or (ch in ")}]" and -1 or 0)
        i += 1
        if depth == 0 and (ch == " " or ch == ")"):
            break
    m = _OPCODE.match(rest[i:])
    opcode = m.group(1) if m else ""
    shape = _SHAPE.search(rest[:i])
    label = " ".join(x for x in (head[:80], shape and shape.group(0), opcode)
                     if x)
    return label, opcode


def op_seconds(trace: dict) -> dict[str, float]:
    """Device seconds per op inside the window, averaged over the device
    planes, containers left out."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    tot: dict[str, float] = {}
    for p in planes:
        for name, t, d, _s in op_events(p):
            label, opcode = op_label(name)
            if opcode in CONTAINERS:
                continue
            a, e = max(t, lo), min(t + d, hi)
            if e > a:
                tot[label] = tot.get(label, 0.0) + (e - a) / 1e9 / len(planes)
    return tot


def matching_seconds(trace: dict, kernel: str) -> float:
    """Device seconds inside the window of the calls of one kernel,
    averaged over the device planes."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    total = 0.0
    for p in planes:
        for name, t, d, stats in op_events(p):
            if kernels.matches(name, stats, kernel):
                a, e = max(t, lo), min(t + d, hi)
                if e > a:
                    total += (e - a) / 1e9 / len(planes)
    return total


def breakdown(trace: dict, top: int = 10) -> dict:
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle_gaps(trace)[:top]]}
