"""End-to-end driver: serve the REDUCED qwen2-vl JAX model with batched
multimodal requests through the TCM engine — sized for the CPU, where the
paged attention runs on the pure-JAX gather path. The published widths
serve on a TPU with compiled Pallas kernels through
``python -m repro.launch.serve --executor real --full-width`` (or
``python chip_smoke.py``, which also checks the kernels on the device).

Every token is actually computed — the batched paged-KV execution path
runs each engine iteration as one packed prefill call plus one fused
decode step over the whole running set (block tables from the engine
allocator, greedy tokens fed back); engine timing comes from measured
wall-clock. Pass executor_kind="real-legacy" for the sequential
per-request oracle the batched path is benchmarked against.

  PYTHONPATH=src python examples/serve_real_model.py
"""
from repro.launch.serve import serve
from repro.serving.metrics import fmt_table, summarize
from repro.serving.workload import WorkloadConfig

wl = WorkloadConfig(
    mix="MH", rate=20.0, num_requests=12, seed=3,
    # shrink sizes so the reduced model's 256-token window fits
    text_tokens_log_mu=3.0, text_tokens_log_sigma=0.5,
    image_patches=48, video_frames_min=2, video_frames_max=4,
    video_patches_per_frame=16,
    out_tokens_log_mu=2.0, out_tokens_log_sigma=0.3)

done, engine = serve("qwen2-vl-2b", "tcm", wl, executor_kind="real")
print(fmt_table(summarize(done), "real JAX model (reduced qwen2-vl), TCM"))
print(f"iterations={engine.iterations}  wall(sim)={engine.now:.2f}s  "
      f"completed={len(done)}/12")
