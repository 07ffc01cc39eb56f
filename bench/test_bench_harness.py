"""CPU rehearsal of ``bench/run.py``: the whole run (stack, warm phase,
wall-clock window, drain, reference check, result line) at a reduced
size with the Pallas kernels interpreted, and the refusal to run without
a TPU."""
import io
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

CELL = "qwen2vl2b.mh.steady"
# the cell's own files at CPU-sized widths and context
REHEARSE = {
    "model": {"num_layers": 2, "d_model": 256, "num_heads": 4,
              "num_kv_heads": 2, "d_ff": 512, "vocab_size": 512,
              "max_seq_len": 512},
    "traffic": {"context_cap": 240, "video_frames_min": 1,
                "video_frames_max": 2, "video_patches_per_frame": 64,
                "image_patches": 48, "text_tokens_log_mu": 3.5,
                "text_tokens_log_sigma": 0.8, "out_tokens_log_mu": 1.6,
                "out_tokens_log_sigma": 0.5},
    "peaks": {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}},
    "rows": {"prefill": 1, "decode": 2},
    # the stand-in encoder's eager matmul at one chunk size, not all 256
    "warm": {"encoder_sizes": (256,)},
    "attn_impl": "kernel",
}


def rehearse(trace: bool, seconds: float = 8.0, seed: int = 2**31 + 3,
             **extra) -> tuple[dict, str]:
    import run
    buf = io.StringIO()
    result = run.run_cell(CELL, seed, seconds, trace,
                          rehearse=dict(REHEARSE, **extra), out=buf)
    return result, buf.getvalue()


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_prints_one_result_line(trace):
    result, text = rehearse(trace)
    last = json.loads(text.strip().splitlines()[-1])
    assert last == json.loads(json.dumps(result))
    assert list(last)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(last)
    assert last["correct"] is True and last["attempted"] > 0
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in spec["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    per_layer = {m["name"] for m in spec["per_layer"]
                 if CELL in m.get("workloads", [CELL])}
    names = set(last["metrics"])
    assert "setup_s" in names
    if trace:
        assert names - {"setup_s"} <= per_layer
        assert {"plan_ms_per_iter", "step_ms.decode"} <= names
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert names == e2e
    for m in last["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    for c in last["compared"].values():
        assert c["value"] <= c["limit"]


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr
