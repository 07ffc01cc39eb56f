"""The program's spans as the benchmark reads them (``harness/spans.py``):
idle attribution on a hand-made trace and on a recorded chip trace, the
clock mapping on a profiler trace taken here and on the chip trace, and
the five per-layer quantities in a CPU rehearsal of the cell with the
recorder on."""
import glob
import shutil
import sys
import tempfile
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import spans  # noqa: E402
from harness import trace as tr  # noqa: E402
from test_bench_harness import CELL, REHEARSE  # noqa: E402

from repro.serving import tracing  # noqa: E402


def _made_trace() -> dict:
    """A 100-ns window: the device busy at 10-20 and 50-60, the client
    asleep from 90, and the program's spans nested inside ``bench.engine``."""
    host = [["bench.traced", 0.0, 100.0, {}], ["bench.engine", 0.0, 90.0, {}],
            ["repro.engine.step", 5.0, 75.0, {}], ["repro.step", 30.0, 40.0,
                                                   {}],
            ["repro.step.decode.sync", 55.0, 15.0, {}],
            ["bench.wait", 90.0, 10.0, {}]]
    ops = [["op", 10.0, 10.0, {}], ["op", 50.0, 10.0, {}]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": tr.OPS_LINE,
                                             "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": host}]}]}


def test_idle_by_span_shares_working_idle_by_overlap():
    t = _made_trace()
    assert spans.working_idle(t) == [(0.0, 10.0), (20.0, 50.0), (60.0, 90.0)]
    got = {k: round(v * 1e9, 6) for k, v in spans.idle_by_span(t).items()}
    assert got == {"engine": 5 + 10, "repro.engine.step": 5 + 10 + 10,
                   "repro.step": 20, "repro.step.decode.sync": 10}
    gaps = spans.idle_gaps(t)
    assert [n for n, _g in gaps] == ["engine", "repro.step",
                                     "repro.engine.step"]
    assert sum(g for _n, g in gaps) == pytest.approx(
        sum(g for _n, g in tr.idle_gaps(t)))
    # the harness's own readers see only its annotations
    assert all(not n.startswith("repro.") for n, _a, _b in tr.host_spans(t))


def test_program_spans_land_in_a_profiler_trace_on_one_clock():
    import jax
    import jax.numpy as jnp
    d = tempfile.mkdtemp(prefix="bench-spans-")
    x = jnp.ones((64, 64))
    jax.profiler.start_trace(d)
    tracing.start()
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            for _ in range(20):
                with tracing.span("engine.step"):
                    with tracing.span("step"):
                        (x @ x).block_until_ready()
    finally:
        rec = tracing.stop()
        jax.profiler.stop_trace()
    assert glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    t = spans.load_xplane(d)
    shutil.rmtree(d)
    assert [n for n, _a, _b in spans.program_spans(t)] == \
        ["repro." + s.name for s in rec.spans]
    assert {n for n, _a, _b in tr.host_spans(t)} == {"traced"}
    offset, pairs = spans.clock_offset(
        t, [(s.name, s.start, s.end) for s in rec.spans])
    assert len(pairs) == 40
    for _n, a, b, s, e in pairs:
        assert abs(a - (s + offset)) < 50e3 and abs(b - (e + offset)) < 50e3


def test_rehearsal_reads_the_five_quantities_with_the_recorder_on():
    import io

    import run
    c = run.Cell(CELL, rehearse=REHEARSE, out=io.StringIO())
    c.build(2**31 + 5)
    c.warm()
    try:
        w = c.window(2**31 + 5, 8.0, False, on_open=tracing.start)
    finally:
        rec = tracing.stop()
    until = int((w["rec"].t0 + w["phase"]["window_end"]) * 1e9)
    got = spans.quantities(rec, until, w["engine"].allocator.num_pages)
    assert len(got) == 5
    for name, v in got.items():
        assert isinstance(v, float) and v >= 0, name
    for name in ("padded_token_share", "kv_pages_peak_share"):
        assert 0 < got[name] <= 100, name
    # the live tokens the executor counted are the rows the harness read
    # from the engine's plan (served steps, profiling left out)
    steps = w["rec"].steps
    harness_live = sum(n for s in steps for rows in s["prefill"]
                       for _start, n in rows) + \
        sum(len(s["decode"]) for s in steps)
    assert rec.total("tokens.live.prefill") + \
        rec.total("tokens.live.decode") == harness_live


def _chip_trace() -> dict:
    """A slice of a traced chip run with the recorder on: device ops,
    the harness's and the program's annotations, and (``program_spans``)
    the recorder's spans of the same annotations on ``perf_counter_ns``
    (PERF.md §5 names the run)."""
    return tr.load(BENCH / "data" / "trace-spans.json.gz")


def test_program_spans_cover_the_working_idle_of_a_chip_trace():
    t = _chip_trace()
    by = spans.idle_by_span(t)
    working = sum(e - a for a, e in spans.working_idle(t)) / 1e9
    assert working > 0
    assert sum(by.values()) == pytest.approx(working, rel=1e-9)
    program = sum(v for k, v in by.items() if k.startswith("repro."))
    assert program >= 0.8 * working


def test_recorded_spans_match_their_annotations_on_a_chip_trace():
    t = _chip_trace()
    offset, pairs = spans.clock_offset(t, t["program_spans"])
    assert len(pairs) == len(t["program_spans"]) > 100
    for _n, a, b, s, e in pairs:
        assert abs(a - (s + offset)) < 50e3 and abs(b - (e + offset)) < 50e3
