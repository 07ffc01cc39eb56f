"""Operations and bytes the algorithm needs, from live lengths.

The per-layer counts copy ``repro.launch.analysis`` (``_attn_layer_flops``
and ``_mlp_flops``) with exact causal pair counts in place of an average
context; the two attention kernels' counts are new. Everything counts the
work of real tokens only: padding rows, padded chunk positions and padded
block-table pages are left out, so a kernel that skips padding gains
honestly and one that computes more does not.

Bytes are the least HBM traffic a call needs: each live K/V token read
once per layer, each query read and each output written once.
"""
from __future__ import annotations

KV_BYTES = 2     # bf16 K/V pages
ACT_BYTES = 2    # bf16 queries and outputs


def dims(cfg: dict) -> dict:
    hd = cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]
    return dict(L=cfg["num_layers"], D=cfg["d_model"], H=cfg["num_heads"],
                KV=cfg["num_kv_heads"], hd=hd, F=cfg["d_ff"],
                V=cfg["vocab_size"])


def causal_pairs(start: int, n: int) -> int:
    """(query, key) pairs of n new tokens after ``start`` context tokens:
    query i sees start + i + 1 keys."""
    return n * start + n * (n + 1) // 2


def matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through in the decoder stack (biases and
    norms excluded): q, k, v, o and the three SwiGLU matrices per layer."""
    d = dims(cfg)
    per_layer = (d["D"] * (d["H"] + 2 * d["KV"]) * d["hd"]
                 + d["H"] * d["hd"] * d["D"] + 3 * d["D"] * d["F"])
    return d["L"] * per_layer


def param_count(cfg: dict) -> int:
    """Every weight the served model holds (embedding, head, norms and
    biases included)."""
    d = dims(cfg)
    bias = (d["H"] + 2 * d["KV"]) * d["hd"] if cfg.get("qkv_bias") else 0
    head = 0 if cfg.get("tie_embeddings") else d["D"] * d["V"]
    return (matmul_params(cfg) + d["L"] * (2 * d["D"] + bias)
            + d["V"] * d["D"] + head + d["D"])


def layer_flops(cfg: dict, n: int, pairs: int) -> float:
    """One decoder layer over n new tokens attending ``pairs`` causal
    (query, key) pairs (analysis._attn_layer_flops + _mlp_flops)."""
    d = dims(cfg)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    qkv = 2 * n * D * (H + 2 * KV) * hd
    attn = 4 * pairs * H * hd
    out = 2 * n * H * hd * D
    mlp = 2 * n * D * F * 3
    return qkv + attn + out + mlp


def row_flops(cfg: dict, start: int, n: int) -> float:
    """Model FLOPs of one served row: n tokens after ``start`` tokens of
    context through every layer, plus the LM head on the one position
    whose logits the step produces."""
    d = dims(cfg)
    return (d["L"] * layer_flops(cfg, n, causal_pairs(start, n))
            + 2 * d["D"] * d["V"])


def prefill_attn_cost(cfg: dict, rows) -> tuple[float, float]:
    """(FLOPs, bytes) of one paged prefill attention call over all layers;
    rows: [(context already written, new tokens)]."""
    d = dims(cfg)
    flops = bytes_ = 0.0
    for start, n in rows:
        if n <= 0:
            continue
        flops += 4 * causal_pairs(start, n) * d["H"] * d["hd"]
        bytes_ += (2 * (start + n) * d["KV"] * d["hd"] * KV_BYTES
                   + 2 * n * d["H"] * d["hd"] * ACT_BYTES)
    return flops * d["L"], bytes_ * d["L"]


def decode_attn_cost(cfg: dict, contexts) -> tuple[float, float]:
    """(FLOPs, bytes) of one paged decode attention call over all layers;
    contexts: live tokens per row, the new one included."""
    d = dims(cfg)
    flops = bytes_ = 0.0
    for c in contexts:
        if c <= 0:
            continue
        flops += 4 * c * d["H"] * d["hd"]
        bytes_ += (2 * c * d["KV"] * d["hd"] * KV_BYTES
                   + 2 * d["H"] * d["hd"] * ACT_BYTES)
    return flops * d["L"], bytes_ * d["L"]


def least_time(flops: float, bytes_: float, peak: dict) -> float:
    """Roofline bound: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])
