"""The program's own spans and counters (``repro.serving.tracing``) as the
benchmark reads them.

In a profiler trace each program span is a ``repro.*`` host annotation on
the device ops' clock: ``load_xplane`` keeps them beside the harness's
``bench.*`` annotations, ``idle_gaps`` names the device's idle gaps by the
innermost span of either kind, and ``idle_by_span`` shares out the working
idle time (device idle, harness not waiting for an arrival) by overlap.
``clock_offset`` pairs the annotations with the recorder's spans, which
are on ``time.perf_counter_ns()``, and gives the offset between the two
clocks.

From the recorder's ``Records`` come five per-layer quantities, each over
the spans, counts and samples that end by ``until`` (the window's end, in
``perf_counter_ns``). ``bench/run.py`` neither turns the recorder on nor
keeps ``repro.*`` annotations yet, so no cell reports these; PERF.md §7
names the edits that would, and that fold ``load_xplane``, ``idle_gaps``
and ``_device_idle`` into ``trace.py`` in place of these copies.
"""
from __future__ import annotations

import bisect
import glob
import statistics

from harness import stats
from harness import trace as tr

PROGRAM_PREFIX = "repro."


def load_xplane(trace_dir: str) -> dict:
    """``trace.load_xplane``, keeping the program's ``repro.*`` host
    annotations beside the harness's ``bench.*`` ones. Readers of the
    harness's annotations (``trace.host_spans``) see the same spans as
    from ``trace.load_xplane``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    host_prefixes = (tr.HOST_PREFIX, PROGRAM_PREFIX)
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (tr.OPS_LINE, "XLA Modules"):
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns),
                       {k: v for k, v in e.stats
                        if isinstance(v, (int, float, str))} if device
                       else {}]
                      for e in line.events
                      if device or e.name.startswith(host_prefixes)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def program_spans(trace: dict) -> list[tuple[str, float, float]]:
    """(name with its ``repro.`` prefix, start_ns, end_ns) of every
    program annotation, in start order."""
    out = [(name, t, t + dur)
           for p in trace["planes"] if not p["name"].startswith("/device:")
           for line in p["lines"]
           for name, t, dur, _s in line["events"]
           if name.startswith(PROGRAM_PREFIX)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _labels(trace: dict) -> tuple[list[float], list[str]]:
    """The window's host timeline as steps: ``names[i]`` is the innermost
    annotation (harness or program) open from ``times[i]`` to
    ``times[i + 1]``, ``untraced`` where none is."""
    spans = [s for s in tr.host_spans(trace) if s[0] != "traced"]
    spans += program_spans(trace)
    edges = sorted([(a, 1, -b, i) for i, (_n, a, b) in enumerate(spans)]
                   + [(b, 0, 0, i) for i, (_n, _a, b) in enumerate(spans)])
    times, names, open_ = [], [], []
    for t, opening, _neg_end, i in edges:
        if opening:
            open_.append(i)
        else:
            open_.remove(i)
        name = spans[open_[-1]][0] if open_ else "untraced"
        if times and times[-1] == t:
            names[-1] = name
        else:
            times.append(t)
            names.append(name)
    return times, names


def _name_at(times: list[float], names: list[str], t: float) -> str:
    i = bisect.bisect_right(times, t) - 1
    return names[i] if i >= 0 else "untraced"


def _device_idle(trace: dict) -> list[tuple[float, float]]:
    """Gaps of the first device's busy union inside the window."""
    b = tr.busy(trace)
    lo, hi = b["window_ns"]
    gaps, t = [], lo
    for a, e in (b["unions"][0] if b["unions"] else []) + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, e)
    return gaps


def idle_gaps(trace: dict) -> list[tuple[str, float]]:
    """``trace.idle_gaps`` with the program's spans: each gap of the
    first device, longest first, named by the innermost annotation of
    either kind at its midpoint."""
    times, names = _labels(trace)
    out = [(_name_at(times, names, (a + e) / 2), (e - a) / 1e9)
           for a, e in _device_idle(trace)]
    return sorted(out, key=lambda g: -g[1])


def working_idle(trace: dict) -> list[tuple[float, float]]:
    """The device's idle intervals less the harness's ``wait`` spans
    (engine idle, the client sleeping until the next arrival)."""
    lo, hi = tr.window(trace)
    waits = tr.union((s[1:] for s in tr.host_spans(trace)
                      if s[0] == "wait"), lo, hi)
    out = []
    for a, e in _device_idle(trace):
        for wa, we in waits:
            if we <= a or wa >= e:
                continue
            if wa > a:
                out.append((a, wa))
            a = max(a, we)
        if e > a:
            out.append((a, e))
    return out


def idle_by_span(trace: dict) -> dict[str, float]:
    """Working idle seconds of the traced window per innermost annotation,
    by overlap (``untraced`` where no annotation is open); the values sum
    to the working idle time."""
    times, names = _labels(trace)
    out: dict[str, float] = {}
    for a, e in working_idle(trace):
        j = bisect.bisect_right(times, a) - 1    # the step holding a
        t = a
        while t < e:
            end = min(times[j + 1], e) if j + 1 < len(times) else e
            name = names[j] if j >= 0 else "untraced"
            out[name] = out.get(name, 0.0) + (end - t) / 1e9
            t, j = end, j + 1
    return out


def clock_offset(trace: dict, spans: list) -> tuple[float, list]:
    """Pair each program annotation in ``trace`` with the recorded span it
    stands for and return (offset, pairs): ``offset`` is the median of
    annotation start less recorded start, so a ``perf_counter_ns`` time
    plus ``offset`` lies on the trace's clock; each pair is (name,
    annotation start, annotation end, recorded start, recorded end).

    ``spans`` are the recorder's spans as (name, start, end) in start
    order, names without the prefix; the annotations are the stretch of
    them recorded while the trace ran, in the same order."""
    ann = program_spans(trace)
    want = [n[len(PROGRAM_PREFIX):] for n, _a, _b in ann]
    have = [s[0] for s in spans]
    for k in range(len(have) - len(want) + 1):
        if have[k:k + 16] == want[:16] and have[k:k + len(want)] == want:
            break
    else:
        raise ValueError("the trace's program annotations are no stretch "
                         "of the recorded spans")
    pairs = [(n, a, b, s[1], s[2])
             for (n, a, b), s in zip(ann, spans[k:k + len(want)])]
    return statistics.median(p[1] - p[3] for p in pairs), pairs


# -- per-layer quantities from the recorder's Records --------------------

def _ended(records, until: int) -> list[int]:
    return [i for i, s in enumerate(records.spans) if s.end <= until]


def _ancestor(records, i: int, name: str) -> int | None:
    while i >= 0:
        if records.spans[i].name == name:
            return i
        i = records.spans[i].parent
    return None


def step_host_ms_per_iter(records, until: int) -> float | None:
    """Model step layer: mean milliseconds of a ``step`` span less its
    ``*.sync`` and ``step.encode`` children, the executor's host time per
    iteration while the device has nothing queued."""
    spans = records.spans
    own = {i: spans[i].end - spans[i].start for i in _ended(records, until)
           if spans[i].name == "step"}
    for s in spans:
        if s.parent in own and (s.name.endswith(".sync")
                                or s.name == "step.encode"):
            own[s.parent] -= s.end - s.start
    return stats.mean(own.values()) / 1e6 if own else None


def engine_host_ms_per_iter(records, until: int) -> float | None:
    """Scheduler layer: mean milliseconds of an ``engine.step`` span less
    its ``step`` and ``admission.profile`` descendants: ingest, plan and
    commit."""
    spans = records.spans
    own = {i: spans[i].end - spans[i].start for i in _ended(records, until)
           if spans[i].name == "engine.step"}
    for s in spans:
        if s.name in ("step", "admission.profile"):
            top = _ancestor(records, s.parent, "engine.step")
            if top in own:
                own[top] -= s.end - s.start
    return stats.mean(own.values()) / 1e6 if own else None


def padded_token_share(records, until: int) -> float | None:
    """Model step layer: percent of the served steps' token slots
    (batch x chunk, prefill and decode, admission profiling left out)
    that are padding."""
    live = sum(records.total(f"tokens.live.{k}", until)
               for k in ("prefill", "decode"))
    padded = sum(records.total(f"tokens.padded.{k}", until)
                 for k in ("prefill", "decode"))
    return 100.0 * padded / (live + padded) if live + padded else None


def queue_wait_p90_ms(records, until: int) -> float | None:
    """Scheduler layer: 90th percentile, over the requests first admitted
    by ``until``, of the wall milliseconds from entering the prefill
    queue (``queued``) to that admission (``admitted``)."""
    queued, waits = {}, []
    for name, t, rid in records.events:
        if name == "queued":
            queued.setdefault(rid, t)
        elif name == "admitted" and t <= until and rid in queued:
            waits.append((t - queued[rid]) / 1e6)
    return stats.percentile(waits, 90) if waits else None


def kv_pages_peak_share(records, until: int,
                        num_pages: int) -> float | None:
    """Cache layer: percent of the KV page pool (``num_pages``, the
    engine's ``allocator.num_pages``) in use at the fullest engine step."""
    used = [v for name, t, v in records.gauges
            if name == "kv.pages_used" and t <= until]
    return 100.0 * max(used) / num_pages if used else None


def quantities(records, until: int, num_pages: int) -> dict:
    """The five per-layer quantities by name."""
    out = {f.__name__: f(records, until)
           for f in (step_host_ms_per_iter, engine_host_ms_per_iter,
                     padded_token_share, queue_wait_p90_ms)}
    out["kv_pages_peak_share"] = kv_pages_peak_share(records, until,
                                                     num_pages)
    return out
