"""Serving launcher: run the TCM-Serve engine on a workload.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-vl-2b \
      --policy tcm --mix MH --rate 2.0 --num-requests 200 --executor sim

Executors:
  sim  — cost model derived from the FULL assigned architecture (A100-class
         coefficients); workload-scale scheduler experiments.
  real — the actual JAX model through the batched paged executor: the
         reduced config at a CPU-sized geometry by default (tests,
         examples; Pallas kernels interpreted), or with --full-width the
         published widths at a one-chip geometry (on a TPU: compiled
         kernels, bf16 weights, 4,096 KV pages).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-vl-2b \
      --executor real --full-width --mix ML --rate 50 --num-requests 8

``main`` keeps JAX's persistent compile cache where
JAX_COMPILATION_CACHE_DIR says, or else in ``.jax_cache/`` at the root of
the checkout.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

from repro.configs import get_config, get_reduced
from repro.core.classifier import NaiveClassifier, SmartClassifier
from repro.core.estimator import ImpactEstimator
from repro.core.profiler import WorkloadProfiler
from repro.core.scheduler import make_policy
from repro.serving.engine import Engine, EngineConfig
from repro.serving.executors import ExecutorConfig, ModelExecutor, \
    SimExecutor, cost_model_for_arch, make_cost_model
from repro.serving.metrics import fmt_table, goodput, summarize
from repro.serving.workload import WorkloadConfig, generate, \
    profiling_workload

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at JAX_COMPILATION_CACHE_DIR
    when it is set (JAX reads that itself), else at one fixed directory
    inside the checkout: the path is part of the cache key, so it must not
    move between runs. For entry points only, before their first compile;
    returns the directory in use."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def build_stack(arch: str, executor_kind: str = "sim", *,
                naive_classifier: bool = False, model_preset: str | None = None,
                kv_pages: int | None = None, token_budget: int = 512,
                slo_scale: float = 5.0, full_width: bool = False):
    """(executor, classifier, engine config, profile, estimator) for one
    model. ``full_width`` builds a real executor from the published
    config at a one-chip geometry instead of the reduced one."""
    if executor_kind == "sim":
        cm = (make_cost_model(model_preset) if model_preset
              else cost_model_for_arch(get_config(arch)))
        executor = SimExecutor(cm)
        prof_reqs = profiling_workload()
    else:
        # "real" = batched paged path; "real-legacy" = the seed's
        # sequential dense-slot oracle (token-parity baseline). An
        # explicit kv_pages sizes the executor's paged stores directly —
        # KV capacity decoupled from the max_slots x max_len slot
        # geometry (prefix-cache-heavy configs want far more resident
        # KV than the running set's context windows).
        # Full width: 16 x 4,096-token windows = 4,096 pages of 16, which
        # for qwen2-vl-2b is ~1.9 GB of bf16 KV beside ~3.6 GB of weights
        # on a 16 GB chip.
        exec_cfg = ExecutorConfig(
            max_slots=16, max_len=4096 if full_width else 256,
            legacy=(executor_kind == "real-legacy"),
            num_pages=kv_pages).resolved()
        executor = ModelExecutor(
            get_config(arch) if full_width else get_reduced(arch), exec_cfg)
        prof_reqs = profiling_workload(n_per_modality=8)
        # real mode: the engine's KV capacity IS the resolved executor
        # capacity — one derivation (ExecutorConfig.resolved), so the
        # admission path and the paged stores agree by construction (the
        # default A100-sized kv_pages would build gigabyte page arrays)
        kv_pages = exec_cfg.num_pages
    profile = WorkloadProfiler(executor, arch).build(prof_reqs)
    if executor_kind != "sim":
        # the SLO price table, measured during set-up: the engine then
        # prices every request by lookup, with no prefill on the serve path
        executor.prefill_prices()
    est = ImpactEstimator.train(profile)
    classifier = (NaiveClassifier(est) if naive_classifier
                  else SmartClassifier.train(est, profile))
    cfg_kwargs = dict(token_budget=token_budget, slo_scale=slo_scale)
    if kv_pages is not None:
        cfg_kwargs["kv_pages"] = kv_pages
    engine_cfg = EngineConfig(**cfg_kwargs)
    return executor, classifier, engine_cfg, profile, est


def serve(arch: str, policy: str, workload: WorkloadConfig, *,
          executor_kind: str = "sim", naive_classifier: bool = False,
          model_preset: str | None = None, kv_pages: int | None = None,
          token_budget: int = 512, slo_scale: float = 5.0,
          full_width: bool = False):
    executor, classifier, engine_cfg, _, _ = build_stack(
        arch, executor_kind, naive_classifier=naive_classifier,
        model_preset=model_preset, kv_pages=kv_pages,
        token_budget=token_budget, slo_scale=slo_scale,
        full_width=full_width)
    engine = Engine(make_policy(policy), executor, classifier, engine_cfg)
    reqs = generate(workload)
    done = engine.run(reqs)
    return done, engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-vl-2b")
    ap.add_argument("--policy", default="tcm",
                    choices=["fcfs", "edf", "static", "naive-aging", "tcm"])
    ap.add_argument("--mix", default="MH", choices=["T0", "ML", "MH"])
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--num-requests", type=int, default=200)
    ap.add_argument("--executor", default="sim",
                    choices=["sim", "real", "real-legacy"])
    ap.add_argument("--full-width", action="store_true",
                    help="real executors: serve the published widths at a "
                         "one-chip geometry instead of the reduced model")
    ap.add_argument("--naive-classifier", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    wl = WorkloadConfig(mix=args.mix, rate=args.rate,
                        num_requests=args.num_requests, seed=args.seed)
    done, engine = serve(args.arch, args.policy, wl,
                         executor_kind=args.executor,
                         naive_classifier=args.naive_classifier,
                         full_width=args.full_width)
    s = summarize(done)
    print(fmt_table(s, f"{args.arch} | {args.policy} | {args.mix} "
                       f"@ {args.rate} rps ({args.executor})"))
    print(f"goodput: {goodput(done):.3f} req/s   engine iterations: "
          f"{engine.iterations}   simulated time: {engine.now:.1f}s")


if __name__ == "__main__":
    main()
