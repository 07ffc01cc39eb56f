"""The serve path's recorder (``repro.serving.tracing``): off, it keeps
nothing and builds no span; on, its spans nest where the work happens,
each request's events share its rid in lifecycle order, the token
counters add up to the dispatched shapes, and no decision or token
changes."""
import dataclasses
import itertools
import tracemalloc
import types

import pytest

from repro.core.scheduler import make_policy
from repro.serving import executors, tracing
from repro.serving.engine import Engine
from repro.serving.workload import WorkloadConfig, generate

KINDS = ["sim", "real"]
WORKLOADS = {
    # enough load on a small pool that requests queue and get preempted
    "sim": WorkloadConfig(mix="MH", rate=2.0, num_requests=80, seed=3),
    "real": WorkloadConfig(mix="ML", rate=50.0, num_requests=10, seed=7,
                           out_tokens_log_mu=1.8, out_tokens_log_sigma=0.3,
                           text_tokens_log_mu=3.2, text_tokens_log_sigma=0.5,
                           video_frames_min=1, video_frames_max=2,
                           image_patches=32, video_patches_per_frame=16),
}
ENGINE = {"engine.ingest", "engine.plan", "step", "engine.commit"}
_STACKS: dict = {}
_RECORDED: dict = {}


@pytest.fixture(autouse=True)
def _recorder_off():
    tracing.stop()
    yield
    tracing.stop()


def _stack(kind: str, sim_stack):
    """(executor, classifier, engine config) in the state every run starts
    from. The real executor is the reduced model on a 24-page pool, built
    once (its profiling prefills are the slow part) and reset per run; its
    clock is a counter, so the engine's schedule does not follow the host's
    speed."""
    if kind == "sim":
        ex, clf, cfg = sim_stack[:3]
        return ex.fresh(), clf, dataclasses.replace(cfg, kv_pages=1200)
    if "real" not in _STACKS:
        from repro.launch.serve import build_stack
        _STACKS["real"] = build_stack("chatglm3-6b", "real", kv_pages=24)[:3]
    ex, clf, cfg = _STACKS["real"]
    ex.emitted.clear()
    ex._profile_rate = 1e-4
    return ex, clf, cfg


def _run(kind: str, sim_stack, monkeypatch, on: bool, calls=None):
    """One engine run of the kind's workload with the journal on; returns
    the engine and what the recorder kept (None when off). ``calls``, if
    given, gets (B x C, live tokens) of each call of the real executor's
    jitted step."""
    ex, clf, cfg = _stack(kind, sim_stack)
    with monkeypatch.context() as m:
        if kind == "real":
            m.setattr(executors, "time", types.SimpleNamespace(
                perf_counter=itertools.count(0.0, 1e-3).__next__))
        if calls is not None:
            jitted = ex._prefill_jit

            def spy(params, stores, toks, pos, bt, lengths, new_lens):
                calls.append((toks.size, int(new_lens.sum())))
                return jitted(params, stores, toks, pos, bt, lengths,
                              new_lens)
            m.setattr(ex, "_prefill_jit", spy)
        eng = Engine(make_policy("tcm"), ex, clf,
                     dataclasses.replace(cfg, journal=True))
        if on:
            tracing.start()
        try:
            eng.run(generate(WORKLOADS[kind]))
        finally:
            rec = tracing.stop()
    return eng, rec


def _recorded(kind: str, sim_stack, monkeypatch):
    """The kind's run with the recorder on, made once per module: the
    engine, the records, the tokens emitted and the jitted calls."""
    if kind not in _RECORDED:
        calls: list = []
        eng, rec = _run(kind, sim_stack, monkeypatch, on=True,
                        calls=calls if kind == "real" else None)
        _RECORDED[kind] = (eng, rec,
                           dict(getattr(eng.executor, "emitted", {})), calls)
    return _RECORDED[kind]


def test_off_keeps_nothing_and_builds_no_span(sim_stack, monkeypatch):
    def no_span(*_a, **_k):
        raise AssertionError("a span was built with the recorder off")
    monkeypatch.setattr(tracing, "Span", no_span)
    eng, rec = _run("sim", sim_stack, monkeypatch, on=False)
    assert rec is None and len(eng.finished) > 0
    assert tracing.span("a") is tracing.span("b") is tracing.NULL
    assert not tracing.NULL
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(10_000):
            with tracing.span("engine.step"):
                pass
            tracing.event("queued", "r")
            tracing.count("tokens.live.decode", i)
            tracing.gauge("kv.pages_used", i)
        grown = tracemalloc.take_snapshot().compare_to(before, "filename")
    finally:
        tracemalloc.stop()
    assert not [d for d in grown
                if d.traceback[0].filename == tracing.__file__
                and d.size_diff > 0]


@pytest.mark.parametrize("kind", KINDS)
def test_spans_nest_where_the_work_happens(kind, sim_stack, monkeypatch):
    _eng, rec, _, _ = _recorded(kind, sim_stack, monkeypatch)
    spans = rec.spans
    assert rec.open == [] and spans
    parent = {}
    for s in spans:
        assert 0 < s.start <= s.end
        p = spans[s.parent] if s.parent >= 0 else None
        if p is not None:
            assert p.start <= s.start and s.end <= p.end
        parent.setdefault(s.name, set()).add(p.name if p else None)
    assert parent["engine.step"] == {None}
    for name in ENGINE:
        assert parent[name] == {"engine.step"}, name
    if kind == "sim":
        assert set(parent) == ENGINE | {"engine.step"}
        return
    # SLOs are priced from the table build_stack measured: no request is
    # profiled on the serve path
    assert "admission.profile" not in parent
    assert parent["step.encode"] == {"step"}
    assert parent["step.decode.prep"] == parent["step.decode.sync"] == \
        {"step"}
    assert parent["step.prefill.prep"] == parent["step.prefill.sync"] == \
        {"step"}
    assert parent["step.prefill.dispatch"] == {"step"}
    assert parent["step.decode.dispatch"] == {"step"}


@pytest.mark.parametrize("kind", KINDS)
def test_request_events_share_rid_in_lifecycle_order(kind, sim_stack,
                                                     monkeypatch):
    eng, rec, _, _ = _recorded(kind, sim_stack, monkeypatch)
    first: dict = {}
    for name, t, rid in rec.events:
        assert name in ("queued", "admitted") and rid is not None
        first.setdefault(rid, {}).setdefault(name, t)
    for req in eng.finished:
        assert first[req.rid]["queued"] <= first[req.rid]["admitted"], \
            req.rid
    # admitted once, though preempted requests are admitted again
    admitted = [r for n, _t, r in rec.events if n == "admitted"]
    assert len(admitted) == len(set(admitted))
    if kind == "sim":
        assert any(r.preemptions for r in eng.finished)


def test_token_counters_add_up_to_the_dispatched_shapes(sim_stack,
                                                        monkeypatch):
    eng, rec, _, calls = _recorded("real", sim_stack, monkeypatch)
    # each jitted call is one dispatch span, in order; its parent says
    # whether it served a step or measured an admission profile (none
    # does: the served run prices SLOs from the set-up table)
    dispatches = [s for s in rec.spans if s.name.endswith(".dispatch")]
    assert len(dispatches) == len(calls)
    slots = {"prefill": 0, "profile": 0, "decode": 0}
    live = dict(slots)
    for s, (bc, n) in zip(dispatches, calls):
        label = ("decode" if s.name == "step.decode.dispatch" else
                 "profile" if rec.spans[s.parent].name ==
                 "admission.profile" else "prefill")
        slots[label] += bc
        live[label] += n
    assert slots["prefill"] and slots["decode"], slots
    assert slots["profile"] == live["profile"] == 0, slots
    assert not [s for s in rec.spans if s.name == "admission.profile"]
    for label in ("prefill", "decode"):
        assert rec.total(f"tokens.live.{label}") == live[label]
        assert rec.total(f"tokens.live.{label}") + \
            rec.total(f"tokens.padded.{label}") == slots[label]
    # admission profiling is left out of the counters
    assert {k for k, _t, _n in rec.counts} == {
        f"tokens.{k}.{label}" for k in ("live", "padded")
        for label in ("prefill", "decode")}
    pages = [v for n, _t, v in rec.gauges if n == "kv.pages_used"]
    assert len(pages) == sum(s.name == "engine.step" for s in rec.spans)
    assert 0 < max(pages) <= eng.allocator.num_pages


@pytest.mark.parametrize("kind", KINDS)
def test_recorder_changes_no_decision(kind, sim_stack, monkeypatch):
    on, _rec, on_tokens, _ = _recorded(kind, sim_stack, monkeypatch)
    off, _ = _run(kind, sim_stack, monkeypatch, on=False)

    def outcome(eng):
        return ([(r.rid, r.first_token_time, r.finish_time, r.preemptions)
                 for r in eng.finished], eng.journal.records)
    assert outcome(on) == outcome(off)
    assert on_tokens == getattr(off.executor, "emitted", {})
    if kind == "real":
        assert on_tokens and all(on_tokens.values())


def test_dropped_records_leave_no_cyclic_garbage():
    import gc
    tracing.start()
    for _ in range(100):
        with tracing.span("engine.step"):
            with tracing.span("step"):
                tracing.count("tokens.live.decode", 1)
    rec = tracing.stop()
    assert len(rec.spans) == 200 and rec.open == []
    gc.collect()
    del rec
    assert gc.collect() == 0
