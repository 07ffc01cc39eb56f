"""The benchmark's yardstick on the CPU: traffic from the mix files, the
FLOP and byte counts, the seeded weights, the plain reference against the
program's own forward pass, and the trace reduction on a recorded trace."""
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import flops, traffic  # noqa: E402
from harness import trace as tr  # noqa: E402

CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
TRAFFIC = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _ref():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "decoder_gqa", BENCH / "references" / "decoder_gqa.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _small(cfg_name: str) -> dict:
    """A configuration's own file at CPU-sized widths (same family, same
    rope style, bias and tying)."""
    model = json.loads((BENCH / "configs" / f"{cfg_name}.json").read_text())
    return dict(model["model"], num_layers=2, d_model=128, num_heads=4,
                num_kv_heads=2, d_ff=256, vocab_size=300)


@pytest.mark.parametrize("name", TRAFFIC)
def test_traffic_regenerates_from_seed(name):
    t = traffic.load_traffic(name)
    a = traffic.window_requests(t, 20.0, 2**31 + 7)
    b = traffic.window_requests(t, 20.0, 2**31 + 7)
    c = traffic.window_requests(t, 20.0, 5)
    assert a == b and a != c
    assert [s.arrival for s in a] != [s.arrival for s in c]
    # every seed serves one schedule in another order: the same sizes, and
    # the same gaps between arrivals round the window's circle
    sizes = sorted((s.modality, s.prompt_tokens, s.output_tokens) for s in a)
    assert sizes == sorted((s.modality, s.prompt_tokens, s.output_tokens)
                           for s in c)

    def gaps(reqs):
        t = np.array([s.arrival for s in reqs])
        return np.sort(np.diff(t, append=t[0] + 20.0))
    assert np.allclose(gaps(a), gaps(c))
    cap = t["params"]["context_cap"]
    assert all(s.prompt_tokens + s.output_tokens <= cap for s in a)
    assert all(0 <= s.arrival < 20.0 for s in a)
    assert len(a) == round(t["rate_rps"] * 20.0)


def test_generator_copy_matches_the_program():
    from repro.serving.workload import WorkloadConfig, generate
    p = dict(traffic.DEFAULTS)
    for mix, w in (("MH", (0.5, 0.3, 0.2)), ("T0", (1.0, 0.0, 0.0))):
        p["mix"] = dict(zip(("text", "image", "video"), w))
        ours = traffic.generate(p, 64, 2.0, 11)
        theirs = generate(WorkloadConfig(mix=mix, rate=2.0, num_requests=64,
                                         seed=11))
        assert [(s.rid, s.modality, s.arrival, s.text_tokens, s.mm_units,
                 s.output_tokens, s.mm_hash) for s in ours] == \
            [(r.rid, r.modality.value, r.arrival, r.text_tokens, r.mm_units,
              r.output_tokens, r.mm_hash) for r in theirs]


@pytest.mark.parametrize("name", CONFIGS)
def test_model_flops_per_token_match_parameter_count(name):
    """One token at no context: 2 FLOPs per weight it multiplies through
    (every matrix of the stack plus the LM head) and one causal pair of
    attention per layer; and the weight tally equals the program's."""
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.models.params import param_count
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]
    d = flops.dims(cfg)
    head = d["D"] * d["V"]
    want = 2 * (flops.matmul_params(cfg) + head) + 4 * d["H"] * d["hd"] * d["L"]
    assert flops.row_flops(cfg, 0, 1) == want
    program = param_count(T.model_decls(ModelConfig(
        **{k: v for k, v in cfg.items() if k != "dtype"})))
    assert flops.param_count(cfg) == program
    ref = _ref()
    leaves = ref.top_leaves(cfg) + [
        (p, (d["L"],) + s, sd) for p, s, sd in ref.layer_leaves(cfg)]
    assert sum(math.prod(s) for _p, s, _sd in leaves) == program


def test_attention_counts_follow_live_lengths():
    cfg = _small("qwen2-vl-2b")
    d = flops.dims(cfg)
    f, b = flops.prefill_attn_cost(cfg, [(10, 4), (0, 0)])
    assert f == 4 * (4 * 10 + 10) * d["H"] * d["hd"] * d["L"]
    assert b == d["L"] * (2 * 14 * d["KV"] * d["hd"] * 2
                          + 2 * 4 * d["H"] * d["hd"] * 2)
    f, b = flops.decode_attn_cost(cfg, [7, 0])
    assert f == 4 * 7 * d["H"] * d["hd"] * d["L"]
    peak = {"bf16_flops_per_s": 2.0, "hbm_bytes_per_s": 1.0}
    assert flops.least_time(10.0, 4.0, peak) == 5.0


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_draw_layer_by_layer(name):
    import jax
    import jax.numpy as jnp
    ref, cfg = _ref(), _small(name)
    seed = 2**31 + 99
    w = ref.make_weights(cfg, seed, jnp.bfloat16)
    for layer in range(cfg["num_layers"]):
        one = ref.layer_weights(cfg, seed, layer)
        stacked = jax.tree.map(lambda a: a[layer], w["stages"][0]["b0"])
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool(jnp.array_equal(a, b)), stacked, one))
    top = ref.top_weights(cfg, seed)
    assert all(bool(jnp.array_equal(top[k], w[k])) for k in top)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_the_programs_forward_in_float32(name):
    """Two independent implementations of one architecture agree: the
    reference, and the program's own forward pass given the same weights
    in float32."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    ref, cfg = _ref(), _small(name)
    seed = 3
    mcfg = ModelConfig(**dict({k: v for k, v in cfg.items() if k != "dtype"},
                              dtype=jnp.float32))
    w = ref.make_weights(cfg, seed, jnp.bfloat16)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    toks = np.random.default_rng(0).integers(1, cfg["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        prog, _c, _a = T.forward(w32, mcfg, jnp.asarray(toks[None], jnp.int32))
    got = ref.served_logits(cfg, seed, [toks], [np.arange(40)], mode="f32")[0]
    # the reference draws bf16 weights and upcasts; the program was handed
    # the same values, so both compute in float32 from equal inputs
    np.testing.assert_allclose(np.asarray(got), np.asarray(prog[0]),
                               rtol=2e-4, atol=2e-4)


def _recorded():
    return tr.load(BENCH / "data" / "trace-small.json.gz")


def test_trace_busy_and_idle_on_a_recorded_trace():
    t = _recorded()
    lo, hi = tr.window(t)
    b = tr.busy(t)
    assert b["window_s"] == pytest.approx((hi - lo) / 1e9)
    # busy: union of op intervals, counted here on a 100 ns raster
    plane = tr.device_planes(t)[0]
    grid = np.zeros(int((hi - lo) // 100) + 1, bool)
    for _n, s, d, _st in tr.op_events(plane):
        a, e = max(s, lo), min(s + d, hi)
        if e > a:
            grid[int((a - lo) // 100):int(math.ceil((e - lo) / 100))] = True
    assert b["busy_s"] == pytest.approx(grid.sum() * 100 / 1e9, rel=0.02)
    assert 0 < b["busy_s"] < b["window_s"]
    gaps = tr.idle_gaps(t)
    assert sum(g for _n, g in gaps) == pytest.approx(
        b["window_s"] - b["busy_s"], rel=1e-9)
    assert all(n in ("wait", "engine", "plan", "step", "admission", "stamp",
                     "untraced") for n, _g in gaps)


def test_trace_kernel_sums_on_a_recorded_trace():
    from harness import kernels
    t = _recorded()
    lo, hi = tr.window(t)
    for pattern in (kernels.PREFILL_ATTN, kernels.DECODE_ATTN):
        direct = sum(max(0.0, min(s + d, hi) - max(s, lo))
                     for p in tr.device_planes(t)
                     for n, s, d, st in tr.op_events(p)
                     if kernels.matches(n, st, pattern)) / 1e9
        assert tr.matching_seconds(t, pattern) == pytest.approx(direct)
        assert direct > 0
    ops = tr.op_seconds(t)
    assert sum(ops.values()) >= tr.matching_seconds(t, kernels.DECODE_ATTN)
    bd = tr.breakdown(t)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


@pytest.mark.parametrize("name", TRAFFIC)
def test_warm_up_covers_the_steps_traffic_can_reach(name):
    """Every jit signature the executor's own bucketing gives a step within
    the traffic file's row bounds is among those set-up warms: admission
    profiling of any prompt, served prefill chunks under the token budget
    at any context, decode batches at any context."""
    sys.path.insert(0, str(BENCH.parent / "src"))
    from harness import serve
    from repro.models.config import ModelConfig
    from repro.serving.executors import ExecutorConfig, ModelExecutor
    rows_bound = traffic.load_traffic(name)["rows"]
    cfg = ModelConfig(name="tiny", arch_type="dense", num_layers=1,
                      d_model=32, num_heads=2, num_kv_heads=1, d_ff=64,
                      vocab_size=64)
    ex = ModelExecutor(cfg, ExecutorConfig(max_slots=16, max_len=4096,
                                           page_size=16, num_pages=4096))
    ex._stores = object()
    seen = []

    def record(params, stores, toks, pos, bt, lengths, new_lens):
        seen.append((toks.shape[0], toks.shape[1], bt.shape[1]))
        return np.zeros(toks.shape[0], np.int32), stores
    ex._prefill_jit = record
    engine_cfg = type("Cfg", (), {"token_budget": 512})
    sigs = set(serve.step_signatures(ex, engine_cfg, rows_bound))
    rng = np.random.default_rng(0)
    window = ex.max_len - 8
    for case in range(600):
        kind = case % 3
        if kind == 0:      # admission profiling: one prompt from position 0
            n = int(rng.integers(1, window + 1))
            rows = [(n, 0)]
        elif kind == 1:    # served prefill: chunks under the budget
            b = int(rng.integers(1, rows_bound["prefill"] + 1))
            sizes = rng.multinomial(int(rng.integers(0, 513 - b)),
                                    np.ones(b) / b) + 1
            rows = [(int(min(n, window)),
                     int(rng.integers(0, window - min(n, window) + 1)))
                    for n in sizes]
        else:              # decode: one token at any context
            b = int(rng.integers(1, rows_bound["decode"] + 1))
            rows = [(1, int(rng.integers(1, window))) for _ in range(b)]
        # the rows of one step share the 4,096-page pool, as served
        share = 4096 * 16 // len(rows) - 16
        rows = [(n, min(ws, max(share - n, 0))) for n, ws in rows]
        call = []
        for i, (n, ws) in enumerate(rows):
            rid = f"c{case}-{i}"
            ex.allocator.allocate(rid, ws + n)
            call.append((rid, np.zeros(n, np.int32), ws, ws, n))
        ex._paged_prefill_call(call)
        for rid, *_ in call:
            ex.allocator.free(rid)
        assert seen[-1] in sigs, (rows, seen[-1])
