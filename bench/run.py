"""Run one benchmark cell once, on the chip.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``workloads`` in BENCHMARK.json) names a configuration
(``bench/configs/<config>.json``) and a traffic
(``bench/traffic/<traffic>.json``, which names a mix in ``bench/mixes/``
and its rate). One run:

1. set-up: imports, the stack as ``build_stack`` serves it (the
   benchmark's seeded bf16 weights, admission-time profiling), then one
   run of the served step at every signature the cell's traffic can
   reach, so that nothing compiles in the window; ``setup_s`` is process
   start to window open;
2. window: an open loop on the wall clock for ``--seconds``: each request
   is handed to ``Engine.step`` once it is due, every token is stamped
   when the step that made it returns, TTFT counts from the scheduled send
   time; then a bounded drain until every request sent has its first
   token;
3. check: the program's state is freed and the plain reference scores a
   sample of the finished requests (``harness/check.py``).

``--trace 1`` traces a few seconds of the window with the harness's host
phases annotated, and reports the cell's per-layer metrics (one reader
each in ``bench/metrics/``) instead of its end-to-end ones.

The last line of stdout is one JSON object; the numbers compared for
``correct`` are also the last lines of stderr. Without an accelerator, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

# after the window closes: at most this long to give every request sent its
# first token
DRAIN_S = 90.0
# the traced slice: this long, centred in the window; at ~1 req/s it holds
# arrivals (admission and prefill kernels) and decode steps all but surely
TRACE_S = 8.0
# the reference scores finished requests until this many served tokens
SAMPLE_TOKENS = 300
SAMPLE_REQUESTS = 8


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(2)


class CompileCounter:
    """Sums JAX's compile-path durations (tracing, lowering, backend
    compiles, cache reads) per phase."""

    def __init__(self):
        import jax
        self.totals: dict[str, list] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, fun_name=None, **_kw):
        if "compil" in name or "jaxpr" in name:
            t = self.totals.setdefault(name.rsplit("/", 1)[-1], [0, 0.0])
            t[0] += 1
            t[1] += secs
            if fun_name and "jaxpr_trace" in name:
                # outermost traces only: a step's trace nests its helpers'
                t = self.totals.setdefault(f"trace:{fun_name}", [0, 0.0])
                t[0] += 1
                t[1] += secs

    def snapshot(self) -> dict:
        return {k: list(v) for k, v in self.totals.items()}

    @staticmethod
    def diff(a: dict, b: dict, top: int = 8) -> dict:
        """Counts and seconds added between two snapshots; of the traces
        by function, the ``top`` most costly."""
        d = {k: [v[0] - a.get(k, [0, 0.0])[0],
                 round(v[1] - a.get(k, [0, 0.0])[1], 6)]
             for k, v in b.items() if v[0] != a.get(k, [0, 0.0])[0]}
        traces = sorted((k for k in d if k.startswith("trace:")),
                        key=lambda k: -d[k][1])
        for k in traces[top:]:
            del d[k]
        return d


def configure_jax() -> str:
    """The persistent compile cache at a fixed path inside the checkout,
    caching every executable (JAX skips those that compile in under a
    second unless told otherwise)."""
    import jax
    cache = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def end_to_end(rec, phase: dict, names: list[str]) -> tuple[dict, dict]:
    """The cell's end-to-end metrics from the wall-clock stamps, and the
    counts behind them."""
    from harness.stats import percentile
    w_end, d_end = phase["window_end"], phase["drain_end"]
    ttft, sand, missing = {}, [], 0
    gaps, emitted = [], 0
    for rid, (sched, _sent) in rec.sends.items():
        st = rec.stamps[rid]
        if st:
            ttft[rid] = st[0] - sched
        else:
            ttft[rid] = d_end - sched     # a lower bound: never served
            missing += 1
        if rec.specs[rid].modality == "text":
            sand.append(ttft[rid])
        emitted += sum(1 for t in st if t <= w_end)
        gaps += [b - a for a, b in zip(st, st[1:]) if b <= w_end]
    values = {
        "ttft_p90_s": percentile(ttft.values(), 90),
        "sand_ttft_p50_s": percentile(sand, 50),
        "itl_p99_ms": percentile(gaps, 99) * 1e3,
        "output_tok_s": emitted / w_end,
    }
    counts = {"sent": len(rec.sends), "missing_first_token": missing,
              "sand": len(sand), "gaps": len(gaps),
              "tokens_in_window": emitted,
              "ttft_p50_s": percentile(ttft.values(), 50),
              "ttft_p99_s": percentile(ttft.values(), 99),
              "itl_p50_ms": percentile(gaps, 50) * 1e3}
    return {k: values[k] for k in names if k in values}, counts


class Cell:
    """One cell's files and the system built for it, reusable across
    windows (``control.py`` serves several seeds in one process)."""

    def __init__(self, cell_name: str, *, root: Path = ROOT,
                 rehearse: dict | None = None, out=sys.stdout):
        self.out, self.rehearse = out, rehearse
        bench_json = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench_json["workloads"]}
        if cell_name not in cells:
            fail(f"no workload {cell_name!r} in BENCHMARK.json")
        self.name, self.cell = cell_name, cells[cell_name]
        import jax
        self.cache_dir = configure_jax() if rehearse is None else "(off)"
        self.devs = jax.devices()
        if rehearse is None and (self.devs[0].platform != "tpu"
                                 or len(self.devs) < self.cell["chips"]):
            fail(f"needs {self.cell['chips']} TPU chip(s); JAX found "
                 f"{len(self.devs)} {self.devs[0].platform} device(s)")
        self.dev = self.devs[0]
        self.compiles = CompileCounter()
        from harness import traffic
        self.cfg_file = json.loads(
            (BENCH / "configs" / f"{self.cell['config']}.json").read_text())
        self.ref = load_module(
            BENCH / "references" / f"{self.cfg_file['reference']}.py")
        self.traffic = traffic.load_traffic(self.cell["traffic"], BENCH)
        self.overrides = None
        peaks = json.loads((BENCH / "harness" / "peaks.json").read_text())
        if rehearse is not None:
            self.overrides = rehearse.get("model")
            peaks = rehearse.get("peaks", peaks)
            self.traffic["params"].update(rehearse.get("traffic", {}))
            self.traffic["rows"].update(rehearse.get("rows", {}))
        self.model = dict(self.cfg_file["model"], **(self.overrides or {}))
        self.limit = float(self.cfg_file["correct"]["logit_gap"])
        self.peak = peaks.get(self.dev.device_kind)
        self.per_layer = [m for m in bench_json["per_layer"]
                          if cell_name in m.get("workloads", [cell_name])]
        self.e2e_names = [m["name"] for m in bench_json["end_to_end"]
                          if cell_name in m.get("workloads", [cell_name])]
        self.units = {m["name"]: m["unit"] for m in bench_json["end_to_end"]}
        self.say(f"device: {self.dev.platform} {self.dev.device_kind} "
                 f"x{len(self.devs)}; compile cache {self.cache_dir}")

    def say(self, *a) -> None:
        print(*a, file=self.out, flush=True)

    def build(self, seed: int) -> None:
        """The stack with ``seed``'s weights, instrumented."""
        import jax

        from harness import serve
        t0 = time.perf_counter()
        self.executor, self.classifier, self.engine_cfg, weights_s = \
            serve.build(self.cfg_file, seed, self.ref.make_weights,
                        full_width=self.rehearse is None,
                        overrides=self.overrides)
        stack_s = time.perf_counter() - t0
        ex = self.executor
        if self.rehearse is None and ex.attn_impl != "kernel":
            fail(f"executor attention is {ex.attn_impl!r}, not 'kernel'")
        if self.rehearse is not None and self.rehearse.get("attn_impl"):
            # CPU rehearsal: the Pallas kernels interpreted, as the chip
            # runs them compiled (the jitted step reads attn_impl when
            # traced)
            ex.attn_impl = self.rehearse["attn_impl"]
            ex._prefill_jit = jax.jit(ex._prefill_step, donate_argnums=(1,))
        if self.rehearse is not None and self.rehearse.get("fault"):
            self.rehearse["fault"](ex)
        self.rec_box, self.annotate = [serve.Records()], [False]
        serve.instrument_executor(ex, self.rec_box, self.annotate)
        self.warm_sigs: set = set()
        self.say(f"set-up: stack {stack_s:.3f} s (weights {weights_s:.3f})")

    def warm(self) -> None:
        """Compile the window's shapes: every step signature the cell's
        traffic can reach (``serve.step_signatures``), each run once,
        unpaced (``serve.warm_steps``)."""
        from harness import serve
        ex = self.executor
        t0 = time.perf_counter()
        sigs = serve.step_signatures(ex, self.engine_cfg,
                                     self.traffic["rows"])
        serve.warm_steps(ex, sigs, **({} if self.rehearse is None else
                                      self.rehearse.get("warm", {})))
        self.warm_sigs = set(sigs)
        self.say(f"warm: {len(sigs)} step signatures and the encoder's "
                 f"chunk sizes in {time.perf_counter() - t0:.3f} s")

    def reseed(self, seed: int) -> None:
        """Serve ``seed``'s weights from here on (same shapes, same
        compiled steps)."""
        import jax
        self.executor.params = None
        self.executor.params = jax.block_until_ready(self.ref.make_weights(
            self.model, seed, self.executor.cfg.dtype))

    def window(self, seed: int, seconds: float, trace: bool,
               on_open=None) -> dict:
        """One window of ``seconds`` on a fresh engine; returns what it
        recorded."""
        from harness import serve, traffic
        specs = traffic.window_requests(self.traffic, seconds, seed)
        engine = serve.make_engine(self.executor, self.classifier,
                                   self.engine_cfg)
        rec = serve.Records()
        self.rec_box[0] = rec
        serve.instrument_engine(engine, self.rec_box, self.annotate)
        slice_ = None
        if trace:
            lo = max(0.0, seconds / 2 - TRACE_S / 2)
            slice_ = (lo, min(seconds, lo + TRACE_S),
                      tempfile.mkdtemp(prefix="bench-trace-"))
            self.annotate[0] = True
        c0 = self.compiles.snapshot()
        k0 = set(self.executor.recompile_keys)
        phase = serve.open_loop(engine, self.executor, specs, seconds, rec,
                                drain_s=DRAIN_S, trace_slice=slice_,
                                on_window_open=on_open)
        self.annotate[0] = False
        c1 = self.compiles.snapshot()
        new_keys = {k for k in set(self.executor.recompile_keys) - k0
                    if serve.signature(k) not in self.warm_sigs}
        mem = [d.memory_stats() or {} for d in self.devs[:self.cell["chips"]]]
        peak_bytes = max((m.get("peak_bytes_in_use", 0) for m in mem),
                         default=0)
        e2e, counts = end_to_end(rec, phase, self.e2e_names)
        self.report_window(rec, phase, counts, seconds)
        self.say(f"compile path in window: "
                 f"{json.dumps(CompileCounter.diff(c0, c1))}; new jit "
                 f"signatures {sorted(new_keys)}")
        self.say(f"end-to-end: {json.dumps(e2e)}")
        return {"rec": rec, "phase": phase, "slice": slice_, "e2e": e2e,
                "counts": counts, "peak_bytes": int(peak_bytes),
                "engine": engine,
                "new_signatures": sorted(serve.signature(k)
                                         for k in new_keys)}

    def report_window(self, rec, phase, counts, seconds) -> None:
        from harness import stats
        late = [a - s for s, a in rec.sends.values()]
        done = sum(1 for s in rec.terminal.values() if s == "finished")
        self.say(f"window: {seconds} s at {self.traffic['rate_rps']} req/s;"
                 f" sent {counts['sent']}, finished {done}, window closed at "
                 f"{phase['window_end']:.3f} s, drain to "
                 f"{phase['drain_end']:.3f} s; {json.dumps(counts)}")
        self.say(f"generator lateness s: p50 {stats.percentile(late, 50):.6f}"
                 f" p99 {stats.percentile(late, 99):.6f} max "
                 f"{max(late, default=0):.6f}")

    def per_layer_metrics(self, w: dict, save_trace: str | None):
        from harness import trace as trace_mod
        tr = trace_mod.load_xplane(w["slice"][2])
        shutil.rmtree(w["slice"][2], ignore_errors=True)
        if save_trace:
            trace_mod.save(tr, Path(save_trace))
        b = trace_mod.busy(tr)
        ctx = types.SimpleNamespace(
            rec=w["rec"], phase=w["phase"], trace=tr, busy=b, cfg=self.model,
            peak=self.peak, slice=w["slice"][:2])
        metrics = {}
        for m in self.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return metrics, b, trace_mod.breakdown(tr)

    def free_program(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.executor.params = None
        self.executor._stores = None
        self.executor = self.classifier = None
        gc.collect()

    def score(self, seed: int, rec, *, control: bool = False) -> dict:
        from harness import check
        t0 = time.perf_counter()
        rids = check.sample(rec.finished, rec.specs, seed,
                            min_tokens=SAMPLE_TOKENS,
                            max_requests=SAMPLE_REQUESTS)
        res = check.compare(self.ref, self.model, seed, rec.specs,
                            rec.finished, rids, control=control)
        res["seconds"] = time.perf_counter() - t0
        self.say(f"reference: {res['requests']} requests, {res['tokens']} "
                 f"served tokens, {res.get('mismatched_tokens', 0)} not the "
                 f"reference's argmax, gap {res['logit_gap']!r}"
                 + (f", control gap {res['control_gap']!r}"
                    if control else "") + f", {res['seconds']:.3f} s "
                 f"{json.dumps(res.get('timings', {}))}")
        return res


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, rehearse: dict | None = None,
             out=sys.stdout, save_trace: str | None = None) -> dict:
    """One run of one cell; returns the result object (also printed as
    the last line of ``out``). ``rehearse`` (tests only) runs on the CPU:
    {"model": config overrides, "traffic": mix overrides, "peaks": a peak
    table, "rows": bounds on the rows of a step to warm, "warm": keyword
    arguments for ``serve.warm_steps``, "attn_impl":
    "kernel" for interpreted kernels, "fault": a callable that breaks the
    executor}."""
    from harness import check
    c = Cell(cell_name, root=root, rehearse=rehearse, out=out)
    if trace and c.per_layer and c.peak is None:
        fail(f"device kind {c.dev.device_kind!r} is not in "
             "harness/peaks.json")
    c.build(seed)
    c.warm()
    setup = {}

    def window_open():
        setup["setup_s"] = time.perf_counter() - T_START
    w = c.window(seed, seconds, trace, on_open=window_open)
    c.say(f"set-up: {setup['setup_s']:.3f} s")
    rec = w.pop("rec")
    device = {"platform": c.dev.platform, "kind": c.dev.device_kind,
              "count": len(c.devs), "memory_peak_bytes": w["peak_bytes"]}
    breakdown = None
    if trace:
        metrics, b, breakdown = c.per_layer_metrics(dict(w, rec=rec),
                                                    save_trace)
        device["busy_s"], device["window_s"] = b["busy_s"], b["window_s"]
        c.say(f"per-layer: {json.dumps(metrics)}")
        c.say(f"breakdown: {json.dumps(breakdown)}")
    else:
        metrics = {k: {"value": float(v), "unit": c.units[k]}
                   for k, v in w["e2e"].items()}
    metrics["setup_s"] = {"value": float(setup["setup_s"]), "unit": "s"}
    failed = sum(1 for rid in rec.sends
                 if not rec.stamps[rid]
                 or rec.terminal.get(rid) in ("rejected", "failed",
                                              "cancelled"))
    del w
    c.free_program()
    res = c.score(seed, rec)
    correct, compared = check.judge(res, c.limit)
    result = {"correct": correct, "attempted": len(rec.sends),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for name, v in compared.items():
        print(f"compared {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="also write the reduced trace (gzipped JSON) here")
    args = ap.parse_args(argv)
    os.chdir(ROOT)
    run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
             save_trace=args.save_trace)


if __name__ == "__main__":
    main()
