"""The system under test and the client that drives it on the wall clock.

``build`` makes the stack as ``repro.launch.serve.build_stack`` serves it
(batched paged ``ModelExecutor``, compiled Pallas kernels on TPU,
``SmartClassifier``, ``EngineConfig``) from a configuration file, with the
benchmark's seeded weights in place of the executor's own draw.
``instrument_executor`` and ``instrument_engine`` time the layer
boundaries from outside (admission profiling, planning, the served step)
without changing what they do. ``open_loop`` is the client: it hands each
request to ``Engine.step`` once it is due and never before, sets the
engine's clock to the wall clock before each step, sleeps when nothing is
due, and stamps every request's tokens on the wall clock after each step.
``step_signatures`` lists the jit signatures of the served step that a
cell's traffic can reach, and ``warm_steps`` runs each once.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax

from harness.traffic import Spec


def model_config(cfg_file: dict, overrides: dict | None = None):
    """The program's ``ModelConfig`` for a configuration file's
    ``model`` block."""
    import jax.numpy as jnp

    from repro.models.config import ModelConfig
    model = dict(cfg_file["model"], **(overrides or {}))
    model["dtype"] = getattr(jnp, model.get("dtype", "bfloat16"))
    return ModelConfig(**model)


def _shapes(tree):
    return jax.tree.map(lambda a: tuple(a.shape), tree)


def build(cfg_file: dict, seed: int, make_weights, *, full_width: bool,
          overrides: dict | None = None):
    """(executor, classifier, engine config, weight seconds) for one
    configuration, through ``build_stack``.

    ``build_stack`` takes an arch name and looks it up; the configuration
    here is the benchmark's own file, so the lookup is pointed at it for
    the call, and the executor's weight draw is replaced by
    ``make_weights`` (the benchmark's seeded weights, checked against the
    executor's declared shapes)."""
    import repro.launch.serve as serve_mod
    import repro.models.params as params_mod
    from repro.models import transformer as T
    from repro.models.params import ParamDecl

    cfg = model_config(cfg_file, overrides)
    model_block = dict(cfg_file["model"], **(overrides or {}))
    decls = T.model_decls(cfg)
    want = jax.tree.map(lambda d: d.shape, decls,
                        is_leaf=lambda x: isinstance(x, ParamDecl))
    timing = {}

    def init_params(d, key, dtype=None):
        if d is not decls and _shapes_of_decls(d) != want:
            return orig_init(d, key, dtype)
        t0 = time.perf_counter()
        w = make_weights(model_block, seed, cfg.dtype)
        w = jax.block_until_ready(w)
        timing["weights_s"] = time.perf_counter() - t0
        if _shapes(w) != want:
            raise ValueError("benchmark weights do not match the program's "
                             f"parameter layout: {_shapes(w)} vs {want}")
        return w

    def _shapes_of_decls(d):
        return jax.tree.map(lambda x: x.shape, d,
                            is_leaf=lambda x: isinstance(x, ParamDecl))

    orig_init = params_mod.init_params
    orig_get = serve_mod.get_config, serve_mod.get_reduced
    serve_mod.get_config = serve_mod.get_reduced = lambda _arch: cfg
    params_mod.init_params = init_params
    try:
        executor, classifier, engine_cfg, _profile, _est = \
            serve_mod.build_stack(cfg.name, "real", full_width=full_width)
    finally:
        params_mod.init_params = orig_init
        serve_mod.get_config, serve_mod.get_reduced = orig_get
    if "weights_s" not in timing:
        raise RuntimeError("the executor did not take the benchmark weights")
    if full_width:
        # the geometry the configuration file states as its deployment
        got = {"max_len": executor.max_len,
               "kv_pages": executor.config.num_pages,
               "page_size": executor.config.page_size,
               "token_budget": engine_cfg.token_budget}
        if got != cfg_file["serving"] or \
                engine_cfg.kv_pages != executor.config.num_pages:
            raise ValueError(f"build_stack serves {got} (engine kv_pages "
                             f"{engine_cfg.kv_pages}), the configuration "
                             f"states {cfg_file['serving']}")
    return executor, classifier, engine_cfg, timing["weights_s"]


def make_engine(executor, classifier, engine_cfg):
    from repro.core.scheduler import make_policy
    from repro.serving.engine import Engine
    return Engine(make_policy("tcm"), executor, classifier, engine_cfg)


def to_request(s: Spec):
    from repro.serving.request import Modality, Request
    return Request(
        rid=s.rid, modality=Modality(s.modality), arrival=s.arrival,
        text_tokens=s.text_tokens, mm_units=s.mm_units,
        output_tokens=s.output_tokens, prompt_tokens=s.prompt_tokens,
        mm_hash=s.mm_hash, shared_prefix_id=s.shared_prefix_id,
        shared_prefix_tokens=s.shared_prefix_tokens, tenant=s.tenant)


@dataclasses.dataclass
class Records:
    """What one phase recorded, on the host clock (seconds after the
    window opened)."""
    t0: float = 0.0                 # perf_counter at window open
    steps: list = dataclasses.field(default_factory=list)     # served steps
    plans: list = dataclasses.field(default_factory=list)     # (t0, t1)
    admissions: list = dataclasses.field(default_factory=list)
    waits: list = dataclasses.field(default_factory=list)     # idle sleeps
    sends: dict = dataclasses.field(default_factory=dict)     # rid -> (due, sent)
    stamps: dict = dataclasses.field(default_factory=dict)    # rid -> token times
    first_chunk: dict = dataclasses.field(default_factory=dict)  # rid -> t
    finished: dict = dataclasses.field(default_factory=dict)  # rid -> tokens
    specs: dict = dataclasses.field(default_factory=dict)     # rid -> Spec
    terminal: dict = dataclasses.field(default_factory=dict)  # rid -> state
    ended: dict = dataclasses.field(default_factory=dict)     # rid -> t

    def now(self) -> float:
        return time.perf_counter() - self.t0


def _trace_options():
    """Device ops and the harness's annotations; no Python call tracing
    (it records every Python call and slows the traced slice's host)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _annotate(name: str, on: bool):
    if on:
        return jax.profiler.TraceAnnotation("bench." + name)
    return contextlib.nullcontext()


def instrument_executor(executor, rec_box: list, annotate: list) -> None:
    """Wrap the engine's two calls into the executor on the instance:
    admission pricing (``isolated_e2e``, whose first call for a request
    runs a profiling prefill of its prompt, cut to the context window) and
    the served step (``run_iteration``). The live rows of each are read
    from the arguments and the requests' own counters: a served prefill
    chunk starts at ``Request.prefilled`` (cut to the context window), a
    decode row attends over ``prompt_tokens + decoded`` tokens, its new
    one included. ``rec_box[0]`` is the Records being filled and
    ``annotate[0]`` whether to emit profiler annotations."""
    orig_e2e = executor.isolated_e2e
    orig_run = executor.run_iteration
    window = executor.max_len
    profiled: set = set()

    def isolated_e2e(req):
        rec = rec_box[0]
        calls = []
        if req.rid not in profiled:
            profiled.add(req.rid)
            calls = [[(0, min(req.prompt_tokens, window - 8))]]
        t0 = rec.now()
        with _annotate("admission", annotate[0]):
            out = orig_e2e(req)
        rec.admissions.append({"t0": t0, "t1": rec.now(), "rid": req.rid,
                               "calls": calls})
        return out

    def run_iteration(prefill_work, decode_reqs, encode_work):
        rec = rec_box[0]
        starts: dict = {}
        rows = []
        for req, chunk in prefill_work:
            start = starts.get(req.rid, req.prefilled)
            starts[req.rid] = start + chunk
            n = min(chunk, window - start - 4)
            if n > 0:
                rows.append((start, n))
        decode = [r.prompt_tokens + r.decoded for r in decode_reqs]
        t0 = rec.now()
        with _annotate("step", annotate[0]):
            out = orig_run(prefill_work, decode_reqs, encode_work)
        t1 = rec.now()
        for req, _chunk in prefill_work:
            rec.first_chunk.setdefault(req.rid, t0)
        rec.steps.append({"t0": t0, "t1": t1,
                          "prefill": [rows] if rows else [],
                          "decode": decode, "encode": len(encode_work)})
        return out

    executor.isolated_e2e = isolated_e2e
    executor.run_iteration = run_iteration


def instrument_engine(engine, rec_box: list, annotate: list) -> None:
    """Time the engine's planning (``_plan``) on the instance."""
    orig_plan = engine._plan

    def plan():
        rec = rec_box[0]
        t0 = rec.now()
        with _annotate("plan", annotate[0]):
            out = orig_plan()
        rec.plans.append((t0, rec.now()))
        return out

    engine._plan = plan


def _stamp(engine, executor, rec: Records, inflight: dict, t: float) -> None:
    """Stamp, at wall-clock ``t``, every token that appeared in the last
    step: a rise of ``Request.decoded`` past the most seen (a recompute
    after preemption re-emits tokens the client already has)."""
    from repro.serving.request import State
    done = []
    for rid, req in inflight.items():
        st = rec.stamps[rid]
        for _ in range(req.decoded - len(st)):
            st.append(t)
        if req.state in (State.FINISHED, State.REJECTED, State.FAILED,
                         State.CANCELLED):
            rec.terminal[rid] = req.state.value
            rec.ended[rid] = t
            if req.state is State.FINISHED:
                rec.finished[rid] = list(executor.emitted.get(rid, ()))
            done.append(rid)
    for rid in done:
        del inflight[rid]


def open_loop(engine, executor, specs: list[Spec], seconds: float,
              rec: Records, *, drain_s: float, trace_slice=None,
              on_window_open=None) -> dict:
    """Drive ``engine`` with ``specs`` on the wall clock for ``seconds``,
    then drain, at most ``drain_s`` more seconds, until every request sent
    has its first token. ``trace_slice`` (start, end, trace_dir) traces
    that part of the window. Returns phase timings."""
    order = sorted(specs, key=lambda s: s.arrival)
    rec.specs.update({s.rid: s for s in order})
    inflight: dict = {}
    i, n = 0, len(order)
    tracing = False
    if on_window_open is not None:
        on_window_open()
    rec.t0 = time.perf_counter()
    traced_cm = None
    while True:
        now = rec.now()
        if trace_slice is not None:
            if not tracing and traced_cm is None and now >= trace_slice[0]:
                jax.profiler.start_trace(trace_slice[2],
                                         profiler_options=_trace_options())
                traced_cm = jax.profiler.TraceAnnotation("bench.traced")
                traced_cm.__enter__()
                tracing = True
            elif tracing and now >= trace_slice[1]:
                traced_cm.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
        if now >= seconds:
            break
        due = []
        while i < n and order[i].arrival <= now:
            s = order[i]
            req = to_request(s)
            due.append(req)
            inflight[s.rid] = req
            rec.sends[s.rid] = (s.arrival, now)
            rec.stamps[s.rid] = []
            i += 1
        if not due and engine.idle:
            nxt = order[i].arrival if i < n else seconds
            with _annotate("wait", tracing):
                t0 = rec.now()
                time.sleep(max(0.0, min(nxt, seconds) - t0))
                rec.waits.append((t0, rec.now()))
            continue
        engine.now = max(engine.now, now)
        with _annotate("engine", tracing):
            engine.step(due)
        with _annotate("stamp", tracing):
            _stamp(engine, executor, rec, inflight, rec.now())
    if tracing:
        traced_cm.__exit__(None, None, None)
        jax.profiler.stop_trace()
    window_end = rec.now()
    # drain: no more arrivals; run until every request sent has its first
    # token (or ended), at most drain_s seconds
    while inflight and rec.now() < window_end + drain_s:
        if all(len(rec.stamps[r]) > 0 for r in inflight):
            break
        if engine.idle:
            break
        engine.now = max(engine.now, rec.now())
        engine.step([])
        _stamp(engine, executor, rec, inflight, rec.now())
    return {"window_end": window_end, "drain_end": rec.now(),
            "sent": i, "unsent": n - i}


def _pow2_upto(n: int) -> list[int]:
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return out + [b]


def step_signatures(executor, engine_cfg, rows: dict) -> list[tuple]:
    """Every (batch, chunk, block-table width) signature of the served step
    that traffic can reach, computed with the executor's own bucketing
    (``_bucket``, ``_bucket_pages``):

    * admission profiling: one row, a chunk of every size up to the
      context window (``isolated_run`` cuts a prompt to ``max_len - 8``),
      its table as wide as the chunk's pages;
    * served prefill: up to ``rows['prefill']`` rows, chunks up to the
      engine's ``token_budget``, tables from the narrowest a chunk of that
      bucket can fill to the context window's;
    * decode (a one-token chunk): up to ``rows['decode']`` rows, tables of
      every width up to the context window's.

    ``rows`` is the traffic file's bound on the rows one step holds at
    the cell's rate. Each signature is (B, C, maxp)."""
    page = executor.allocator.page_size
    bucket, table = executor._bucket, executor._bucket_pages
    window = executor.max_len - 8
    sigs = set()
    for c in _pow2_upto(bucket(window)):
        n = min(c, window)
        sigs.add((1, bucket(n), table(-(-n // page))))
    widths = _pow2_upto(executor.max_pages)
    for b in _pow2_upto(rows["prefill"]):
        for c in _pow2_upto(bucket(min(engine_cfg.token_budget, window))):
            least = table(-(-(c // 2 + 1) // page))
            sigs.update((b, c, w) for w in widths if w >= least)
    for b in _pow2_upto(rows["decode"]):
        sigs.update((b, 1, w) for w in widths)
    return sorted(sigs)


def signature(key: tuple) -> tuple:
    """(B, C, maxp) of one of the executor's ``recompile_keys``: a decode
    step is a one-token chunk of the same jitted step."""
    return (key[1], 1, key[2]) if key[0] == "decode" else tuple(key[1:])


def warm_steps(executor, signatures: list[tuple],
               encoder_sizes=range(1, 257)) -> None:
    """Run the served step once at each signature, unpaced, with the
    arguments ``_paged_prefill_call`` builds for a batch of padding rows
    (no tokens, every table entry the trash page), so each compiles or
    loads from the compile cache now and not in the window; then run the
    stand-in encoder (``encode_chunk``, one eager matmul per chunk size,
    capped at 256) at every size it takes."""
    import numpy as np

    from repro.serving.request import Modality, Request
    jnp = executor.jnp
    if executor._stores is None:
        executor._stores = executor._make_stores()
    trash = executor.allocator.num_pages
    for b, c, w in signatures:
        z = np.zeros((b, c), np.int32)
        out, executor._stores = executor._prefill_jit(
            executor.params, executor._stores, jnp.asarray(z),
            jnp.asarray(z), jnp.asarray(np.full((b, w), trash, np.int32)),
            jnp.asarray(np.zeros((b,), np.int32)),
            jnp.asarray(np.zeros((b,), np.int32)))
        np.asarray(out)
    dummy = Request(rid="warm-encode", modality=Modality.IMAGE, arrival=0.0,
                    text_tokens=1, mm_units=1, output_tokens=1)
    for n in encoder_sizes:
        executor.encode_chunk(dummy, n)
