"""Chip smoke test: serve full-width qwen2-vl-2b on one TPU through the
normal serve path, then check the compiled Pallas kernels against the
pure-JAX gather path on the device.

  python chip_smoke.py              # one TPU chip, published widths
  python chip_smoke.py --rehearse   # CPU: reduced model, interpreted kernels

Phases, all in this one process (nothing is spawned):

1. set-up: ``launch.serve.build_stack(..., full_width=True)`` builds the
   batched paged ``ModelExecutor`` from the published config (bf16 weights
   drawn from a seed, 4,096 KV pages of 16 tokens) and profiles it at
   admission, one compile per power-of-two chunk bucket;
2. serve: ``N_REQUESTS`` seeded text and image requests through
   ``Engine.run`` under the TCM policy. Every request must FINISH with its
   full count of emitted tokens; none may be rejected, failed or cancelled;
3. kernel vs gather: one batched prefill and ``DECODE_STEPS`` decode steps
   through ``transformer.forward`` with the served weights (query
   projection tempered, see ``tempered_params``), once per attention path,
   decode inputs teacher-forced from the gather path. Last-position logits
   must stay within ``LOGIT_ATOL``, and greedy tokens must agree at every
   position except a near-tie: where the gather path's top-1 margin is at
   most twice the largest logit difference seen, two correct paths may
   rank the pair either way (such ties are counted and printed).

Without ``--rehearse`` the script exits non-zero, printing no result, when
JAX finds no TPU, and it refuses to run the executor on the legacy or the
gather path. Nothing here catches an exception: any failure exits non-zero.
The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR says,
or else in ``.jax_cache/`` beside this file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-vl-2b"
SEED = 0
N_REQUESTS = 8
# prompt lengths of the kernel-vs-gather batch (chip; the rehearsal scales
# them to its 256-token window) and the decode steps that follow
COMPARE_PROMPTS = (512, 345, 128, 37)
DECODE_STEPS = 4
# |kernel - gather| bound on last-position logits. Both paths read the same
# bf16 K/V; the kernel keeps scores and probabilities in f32 where the
# gather path rounds both to bf16, and each path rounds every layer's output
# and the logits themselves to bf16. At full width on the CPU (2, 4 and 8
# layers, interpreted kernel) the largest difference was 0.031: one bf16
# ulp of the largest logits, not growing with depth. 0.1 leaves three such
# ulps for 28 layers, an eighth of the logits' standard deviation (~0.8).
LOGIT_ATOL = 0.1


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def memory(phase: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"device memory after {phase}: bytes_in_use "
          f"{stats.get('bytes_in_use', 'not reported')} peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")


def smoke_requests(rehearse: bool):
    """N_REQUESTS seeded text and image requests (no video: the chip's
    4,096-token window is the cell, not long-context video)."""
    from repro.serving.request import Modality
    from repro.serving.workload import WorkloadConfig, generate
    if rehearse:   # sizes that fit the reduced model's 256-token window
        wl = WorkloadConfig(
            mix="MH", rate=50.0, num_requests=4 * N_REQUESTS, seed=SEED,
            text_tokens_log_mu=3.0, text_tokens_log_sigma=0.5,
            image_patches=48, out_tokens_log_mu=2.0,
            out_tokens_log_sigma=0.3)
    else:
        wl = WorkloadConfig(
            mix="MH", rate=50.0, num_requests=4 * N_REQUESTS, seed=SEED,
            out_tokens_log_mu=3.5, out_tokens_log_sigma=0.5)
    reqs = [r for r in generate(wl) if r.modality is not Modality.VIDEO]
    return reqs[:N_REQUESTS]


def serve_phase(rehearse: bool):
    from repro.core.scheduler import make_policy
    from repro.launch.serve import build_stack
    from repro.serving.engine import Engine

    t0 = time.perf_counter()
    executor, classifier, engine_cfg, _, _ = build_stack(
        ARCH, "real", full_width=not rehearse)
    setup_s = time.perf_counter() - t0
    memory("set-up")
    cfg = executor.cfg
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} vocab={cfg.vocab_size} "
          f"max_len={executor.max_len} kv_pages={executor.capacity_pages} "
          f"attn_impl={executor.attn_impl} legacy={executor.legacy}")
    check(not executor.legacy, "executor resolved to the legacy path")
    want_impl = "gather" if rehearse else "kernel"
    check(executor.attn_impl == want_impl,
          f"executor attention is {executor.attn_impl!r}, not {want_impl!r}")

    reqs = smoke_requests(rehearse)
    for r in reqs:
        print(f"  request {r.rid}: {r.modality.value} prompt={r.prompt_tokens}"
              f" output={r.output_tokens}")
    engine = Engine(make_policy("tcm"), executor, classifier, engine_cfg)
    t0 = time.perf_counter()
    done = engine.run(reqs)
    serve_s = time.perf_counter() - t0

    check(not engine.rejected and not engine.aborted,
          "requests ended rejected/failed/cancelled: "
          + ", ".join(f"{r.rid}={r.state.value} ({r.error})"
                      for r in engine.rejected + engine.aborted))
    check(len(done) == len(reqs),
          f"{len(done)} of {len(reqs)} requests finished")
    for r in done:
        n = len(executor.emitted.get(r.rid, ()))
        check(n == r.output_tokens,
              f"{r.rid} emitted {n} tokens, expected {r.output_tokens}")
    keys, bound = len(executor.recompile_keys), executor.recompile_bound()
    check(keys <= bound, f"{keys} jit signatures exceed the bound {bound}")
    tokens = sum(r.output_tokens for r in done)
    print(f"serve: {len(done)}/{len(reqs)} finished, {tokens} tokens emitted, "
          f"{engine.iterations} iterations, recompile_keys={keys} "
          f"(bound {bound})")
    return executor, setup_s, serve_s


def tempered_params(executor):
    """The served weights with every query projection scaled by a power of
    two (exact in bf16). ``init_params`` draws wq and wk at fan-ins of H
    and KV heads, so random-init attention logits have a standard
    deviation near d_model / sqrt(H * KV), ~313 at qwen2-vl-2b's widths:
    attention is then a hard argmax whose near-ties flip on any rounding,
    and two correct paths disagree by whole logits. Scaled to unit size,
    as in a trained model, the comparison measures the kernels instead."""
    import math

    import jax
    cfg = executor.cfg
    t = 2.0 ** -round(math.log2(
        cfg.d_model / math.sqrt(cfg.num_heads * cfg.num_kv_heads)))
    params = jax.tree.map(lambda a: a, executor.params)
    for stage in params["stages"]:
        for block in stage.values():
            block["attn"]["wq"] = block["attn"]["wq"] * t
    return params


def compare_attention_paths(executor):
    """Kernel vs gather at the served widths and weights (query projection
    tempered): one batched prefill, then DECODE_STEPS decode steps fed the
    gather path's greedy tokens. Returns (max |logit diff|, token matches,
    positions compared)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, T, page = executor.cfg, executor.T, executor.allocator.page_size
    scale = min(1.0, (executor.max_len // 2) / max(COMPARE_PROMPTS))
    lens = np.array([max(1, int(n * scale)) for n in COMPARE_PROMPTS],
                    np.int32)
    B = len(lens)
    C = executor._bucket(int(lens.max()))
    mp = executor._bucket(-(-(int(lens.max()) + DECODE_STEPS) // page))
    check(B * mp <= executor.capacity_pages, "comparison outgrows the pool")
    bt = jnp.asarray(np.arange(B * mp, dtype=np.int32).reshape(B, mp))
    rng = np.random.default_rng(SEED)
    toks = np.zeros((B, C), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(1, cfg.vocab_size, n)
    pos = np.broadcast_to(np.arange(C, dtype=np.int32), (B, C))

    def step(params, stores, toks, pos, lengths, new_lens, impl):
        cache = {"stages": stores, "block_table": bt, "lengths": lengths,
                 "new_lens": new_lens}
        logits, new_cache, _ = T.forward(
            params, cfg, toks, positions=pos, cache=cache,
            last_pos=jnp.maximum(new_lens - 1, 0), attn_impl=impl)
        return logits[:, 0].astype(jnp.float32), new_cache["stages"]

    step = jax.jit(step, static_argnames="impl", donate_argnums=(1,))
    params = tempered_params(executor)
    ones = np.ones(B, np.int32)
    logits = {}
    feed = None          # the gather path's greedy tokens, per decode step
    for impl in ("gather", "kernel"):
        stores = executor._make_stores()
        lg, stores = step(params, stores, toks, pos,
                          np.zeros(B, np.int32), lens, impl=impl)
        out = [np.asarray(lg)]
        for j in range(DECODE_STEPS):
            tok = out[-1].argmax(-1) if feed is None else feed[j]
            lg, stores = step(params, stores,
                              tok[:, None].astype(np.int32),
                              (lens + j)[:, None], lens + j, ones, impl=impl)
            out.append(np.asarray(lg))
        logits[impl] = np.stack(out)          # (1 + DECODE_STEPS, B, V)
        if feed is None:
            feed = [o.argmax(-1) for o in out]
    ref, ker = logits["gather"], logits["kernel"]
    check(np.isfinite(ker).all(), "kernel path produced non-finite logits")
    diff = float(np.abs(ker - ref).max())
    same = ker.argmax(-1) == ref.argmax(-1)
    top2 = np.sort(ref, axis=-1)[..., -2:]
    margin = top2[..., 1] - top2[..., 0]
    tie = margin <= 2 * diff
    print(f"kernel vs gather: prompts={lens.tolist()} decode_steps="
          f"{DECODE_STEPS} max|dlogit|={diff:.6f} (atol {LOGIT_ATOL}) "
          f"logit std={float(ref.std()):.4f} greedy tokens agree "
          f"{int(same.sum())}/{same.size}, near-ties {int(tie.sum())}")
    if not same.all():
        print("  gather top-1 margin where tokens differ: "
              f"{margin[~same].tolist()}")
    check(diff <= LOGIT_ATOL,
          f"kernel and gather logits differ by {diff} > {LOGIT_ATOL}")
    check(bool((same | tie).all()),
          "kernel and gather greedy tokens differ outside a near-tie")
    return diff, int(same.sum()), same.size


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the reduced size (tests only)")
    args = ap.parse_args()

    from repro.launch.serve import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); "
                 "--rehearse runs the CPU rehearsal")
    from repro.kernels import ops
    check(args.rehearse or not ops.interpret_mode(),
          "Pallas kernels would run interpreted on the device")
    compile_s = [0.0]

    def on_event(name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs
    jax.monitoring.register_event_duration_secs_listener(on_event)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}")

    executor, setup_s, serve_s = serve_phase(args.rehearse)
    memory("serve")
    compile_serve = compile_s[0]
    # the serve stores go before the comparison builds its own
    executor._stores = None
    t0 = time.perf_counter()
    compare_attention_paths(executor)
    compare_s = time.perf_counter() - t0
    memory("compare")
    print(f"seconds: setup {setup_s:.2f} serve {serve_s:.2f} compare "
          f"{compare_s:.2f}; backend compile {compile_serve:.2f} in set-up "
          f"and serve, {compile_s[0] - compile_serve:.2f} in compare")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
