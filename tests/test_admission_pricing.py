"""SLO pricing from the prefill table (``ModelExecutor.prefill_prices``):
measured once, at set-up or on first use, each signature's second call
kept; ``isolated_e2e`` is then a lookup that dispatches nothing, on
ingest and on repricing alike."""
import types

import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.core.scheduler import make_policy
from repro.serving import executors, tracing
from repro.serving.engine import Engine
from repro.serving.executors import ExecutorConfig, ModelExecutor
from repro.serving.request import Modality, Request
from repro.serving.workload import WorkloadConfig, generate

WORKLOAD = WorkloadConfig(mix="ML", rate=50.0, num_requests=10, seed=7,
                          out_tokens_log_mu=1.8, out_tokens_log_sigma=0.3,
                          text_tokens_log_mu=3.2, text_tokens_log_sigma=0.5,
                          video_frames_min=1, video_frames_max=2,
                          image_patches=32, video_patches_per_frame=16)
_STACK: dict = {}


@pytest.fixture(autouse=True)
def _recorder_off():
    tracing.stop()
    yield
    tracing.stop()


def _stack():
    """(executor, classifier, engine config) of the reduced real stack,
    built once by ``build_stack`` (which measures the table)."""
    if not _STACK:
        from repro.launch.serve import build_stack
        _STACK["real"] = build_stack("chatglm3-6b", "real", kv_pages=48)[:3]
    ex, clf, cfg = _STACK["real"]
    ex.emitted.clear()
    return ex, clf, cfg


def _text(n: int, rid: str = None, output_tokens: int = 3) -> Request:
    return Request(rid=rid or f"t{n}", modality=Modality.TEXT, arrival=0.0,
                   text_tokens=n, prompt_tokens=n,
                   output_tokens=output_tokens)


def test_served_run_prices_without_dispatching(monkeypatch):
    ex, clf, cfg = _stack()
    assert ex._prefill_prices is not None
    table = dict(ex._prefill_prices)
    calls = []                  # names of the spans open at each call
    jitted = ex._prefill_jit

    def spy(*args):
        calls.append({tracing._rec.spans[i].name for i in tracing._rec.open})
        return jitted(*args)
    monkeypatch.setattr(ex, "_prefill_jit", spy)
    eng = Engine(make_policy("tcm"), ex, clf, cfg)
    repriced = []
    reprice = eng._reprice

    def counted_reprice(req):
        before = len(calls)
        reprice(req)
        repriced.append(len(calls) - before)
    monkeypatch.setattr(eng, "_reprice", counted_reprice)
    reqs = generate(WORKLOAD)
    assert all(r.slo == float("inf") for r in reqs)
    tracing.start()
    try:
        eng.run(reqs)
        # a claim that diverged from the ingest advisory reprices the SLO
        req = _text(100, rid="repriced")
        req.slo_from_engine = True
        req.cached_prefix_tokens = 48
        eng._reprice(req)
    finally:
        rec = tracing.stop()
    assert len(eng.finished) == len(reqs)
    assert all(r.slo_from_engine and 0 < r.slo < float("inf")
               for r in eng.finished)
    assert req.slo == cfg.slo_scale * ex.isolated_e2e(req) > 0
    assert sum(s.name == "engine.ingest" for s in rec.spans) > 0
    assert not [s for s in rec.spans if s.name == "admission.profile"]
    assert calls and all(c == {"engine.step", "step",
                               "step.prefill.dispatch"}
                         or c == {"engine.step", "step",
                                  "step.decode.dispatch"} for c in calls)
    assert repriced and not any(repriced)
    assert ex._prefill_prices == table


def test_price_is_the_table_entry_of_the_signature(monkeypatch):
    ex = _stack()[0]
    table = ex.prefill_prices()
    window = ex.max_len - 8
    # one entry per power-of-two chunk up to the window's bucket
    assert sorted(table) == sorted(
        ex._profile_signature(min(1 << k, window)) for k in range(9))
    assert all(p > 0 for p in table.values())

    def no_measure(*_a):
        raise AssertionError("a price lookup ran a prefill")
    monkeypatch.setattr(ex, "_measure_prefill", no_measure)
    last = 0.0
    for n in range(1, ex.max_len + 40):
        req = _text(n)
        base = table[ex._profile_signature(min(n, window))]
        price = ex.isolated_e2e(req)
        assert price == base * (1 + 0.1 * req.output_tokens)
        assert price >= last, n
        last = price
        req.cached_prefix_tokens = n // 2
        assert ex.isolated_e2e(req) == pytest.approx(
            base * (n - n // 2) / n * (1 + 0.1 * req.output_tokens))
    assert ex.isolated_e2e(_text(0)) == 0.0


def _fake_clock_executor(monkeypatch, legacy: bool):
    """A fresh small executor whose jitted steps return at once, on a
    clock that reads 1 ms per call, adds C us of work to each prefill of
    C tokens and 100 s to the first call at a signature (a compile)."""
    ex = ModelExecutor(get_reduced("chatglm3-6b"),
                       ExecutorConfig(max_slots=2, max_len=64,
                                      legacy=legacy))
    clock = {"t": 0.0}
    seen: set = set()

    def read():
        clock["t"] += 1e-3
        return clock["t"]

    def charge(shape):
        clock["t"] += shape[1] * 1e-6 + (100.0 if shape not in seen else 0)
        seen.add(shape)

    def prefill_jit(params, stores, toks, pos, bt, lengths, new_lens):
        charge((toks.shape[0], toks.shape[1], bt.shape[1]))
        return jnp.zeros((toks.shape[0],), jnp.int32), stores

    def legacy_jit(params, toks, positions, cache, q_start):
        charge(toks.shape)
        return jnp.zeros((1, toks.shape[1], 1)), cache, None
    monkeypatch.setattr(executors, "time",
                        types.SimpleNamespace(perf_counter=read))
    monkeypatch.setattr(ex, "_prefill_jit", prefill_jit)
    monkeypatch.setattr(ex, "_legacy_jit", legacy_jit)
    return ex, seen


@pytest.mark.parametrize("legacy", [False, True])
def test_table_keeps_each_signatures_second_call(monkeypatch, legacy):
    ex, seen = _fake_clock_executor(monkeypatch, legacy)
    table = ex.prefill_prices()
    window = ex.max_len - 8
    assert len(table) == 7 == len(seen)
    for k in range(7):
        n = min(1 << k, window)
        # one clock read of 1 ms inside the call, plus its n us of work
        assert table[ex._profile_signature(n)] == pytest.approx(
            1e-3 + (ex._bucket(n) if not legacy else n) * 1e-6)


@pytest.mark.parametrize("legacy", [False, True])
def test_table_is_built_on_first_use_and_only_then(monkeypatch, legacy):
    ex, _seen = _fake_clock_executor(monkeypatch, legacy)
    measured = []
    measure = ex._measure_prefill

    def counted(req, n):
        measured.append(n)
        return measure(req, n)
    monkeypatch.setattr(ex, "_measure_prefill", counted)
    assert ex._prefill_prices is None and not measured
    first = ex.isolated_e2e(_text(40))
    assert sorted(measured) == sorted(2 * [1, 2, 4, 8, 16, 32, 56])
    assert [ex.isolated_e2e(_text(n)) for n in (40, 3, 56, 500)] == [
        first, *(ex.prefill_prices()[ex._profile_signature(min(n, 56))]
                 * (1 + 0.1 * 3) for n in (3, 56, 500))]
    assert len(measured) == 14
    assert ex.allocator.used_pages == 0 and not ex.slot_of
    assert not ex._prompt_cache and not ex.emitted
    if legacy:
        return
    # a new page size changes the signatures: the table is measured anew
    from repro.cache import BlockAllocator
    ex.bind_allocator(BlockAllocator(num_pages=ex.allocator.num_pages,
                                     page_size=8))
    assert ex._prefill_prices is None and len(measured) == 14
    ex.isolated_e2e(_text(40))
    assert len(measured) == 28
    assert set(ex._prefill_prices) == {ex._profile_signature(n)
                                       for n in range(1, 57)}

