"""Plain reference for decoder-only transformers with grouped-query
attention, RMSNorm, RoPE and a SwiGLU MLP (Qwen2 / Qwen2-VL's language
model, ChatGLM3), and the seeded weights every run serves.

It imports nothing of the program. The weights are the benchmark's own:
``make_weights`` draws them on the device in one jitted call, in the
served dtype and in the layout the program's parameter tree has, and the
reference regenerates each layer's slice from the same seed with
``layer_weights`` (the same draw, key for key), so it never reads an array
the program holds.

The forward pass follows the published descriptions, in float32 at the
``highest`` matmul precision, one layer at a time over all sampled
sequences so that it fits beside nothing else on one chip. Departures,
each forced by the program's inputs or equivalent to the published form:

* RMSNorm gains are stored as ``1 + w`` (the program's parameterisation;
  the same function of a different stored number).
* Qwen2-VL's M-RoPE: every token, vision tokens included, takes one 1-D
  position for all three sections, because the program's vision frontend
  is a stub whose patches arrive as prompt tokens. M-RoPE with equal
  temporal, height and width positions is 1-D RoPE over the full head dim.
* ChatGLM3 rotates the first half of each head. Its code pairs adjacent
  columns; this reference (like the program) pairs column i with
  i + hd/4. The two differ by a fixed permutation of the rotated q/k
  columns, which a checkpoint converts by permuting wq/wk.

The weight scales are chosen, not published: unit-size activations and
attention logits of standard deviation ~2, as in a trained model, so that
attention is neither uniform nor a hard argmax (an argmax would flip on
rounding and make any two correct paths disagree by whole logits).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# attention-logit scale: q and k entries of standard deviation QK_STD give
# logits of standard deviation QK_STD**2 after the 1/sqrt(hd) scaling
QK_STD = math.sqrt(2.0)
BIAS_STD = 0.2
NORM_STD = 0.1


def dims(cfg: dict) -> dict:
    hd = cfg.get("head_dim") or cfg["d_model"] // cfg["num_heads"]
    return dict(L=cfg["num_layers"], D=cfg["d_model"], H=cfg["num_heads"],
                KV=cfg["num_kv_heads"], hd=hd, F=cfg["d_ff"],
                V=cfg["vocab_size"])


def layer_leaves(cfg: dict) -> list[tuple[str, tuple, float]]:
    """(path, per-layer shape, standard deviation) of one block's weights,
    in a fixed order (the order is part of each leaf's key)."""
    d = dims(cfg)
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    leaves = [
        ("ln1", (D,), NORM_STD),
        ("attn/wq", (D, H, hd), QK_STD / math.sqrt(D)),
        ("attn/wk", (D, KV, hd), QK_STD / math.sqrt(D)),
        ("attn/wv", (D, KV, hd), 1.0 / math.sqrt(D)),
        ("attn/wo", (H, hd, D), 1.0 / math.sqrt(H * hd)),
        ("ln2", (D,), NORM_STD),
        ("mlp/wi", (D, F), 1.0 / math.sqrt(D)),
        ("mlp/wg", (D, F), 1.0 / math.sqrt(D)),
        ("mlp/wo", (F, D), 1.0 / math.sqrt(F)),
    ]
    if cfg.get("qkv_bias"):
        leaves += [("attn/bq", (H, hd), BIAS_STD),
                   ("attn/bk", (KV, hd), BIAS_STD),
                   ("attn/bv", (KV, hd), BIAS_STD)]
    return leaves


def top_leaves(cfg: dict) -> list[tuple[str, tuple, float]]:
    d = dims(cfg)
    leaves = [("embed", (d["V"], d["D"]), 1.0 / math.sqrt(d["D"])),
              ("final_norm", (d["D"],), NORM_STD)]
    if not cfg.get("tie_embeddings"):
        leaves.append(("lm_head", (d["D"], d["V"]), 1.0 / math.sqrt(d["D"])))
    return leaves


def _base_key(seed: int):
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


# standard deviation of a sum of four independent uniform 16-bit integers
_IH_STD = 65536 * math.sqrt(4 / 12)


def _draw(key, shape, std, dtype):
    """Near-normal values of standard deviation ``std``: a centred sum of
    four uniform 16-bit integers (Irwin-Hall), in exact integer arithmetic
    and then one float multiply, so the values are bit-identical however
    the draw is fused, batched or placed (a transcendental normal
    transform can round differently between an eager and a jitted draw)."""
    b = jax.random.bits(key, (2,) + tuple(shape), jnp.uint32)
    s = ((b[0] & 0xFFFF) + (b[0] >> 16) + (b[1] & 0xFFFF)
         + (b[1] >> 16)).astype(jnp.int32) - 131070
    return (s.astype(jnp.float32) * jnp.float32(std / _IH_STD)).astype(dtype)


def _leaf_key(base, group: int, index: int, layer):
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.fold_in(base, group), index), layer)


def _set(tree: dict, path: str, value) -> None:
    *parents, leaf = path.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[leaf] = value


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every weight, in ``dtype``, in the program's tree layout:
    ``{"embed", "stages": [{"b0": <stacked block>}], "final_norm",
    "lm_head"?}``. One jitted call on the default device."""
    d = dims(cfg)

    def build(base):
        tree: dict = {}
        for i, (path, shape, std) in enumerate(top_leaves(cfg)):
            _set(tree, path, _draw(_leaf_key(base, 0, i, 0), shape, std,
                                   dtype))
        block: dict = {}
        for i, (path, shape, std) in enumerate(layer_leaves(cfg)):
            # one layer at a time: a layer's random bits are the only
            # transient (a batched draw would hold every layer's at once)
            stacked = jax.lax.map(
                lambda l, i=i, shape=shape, std=std: _draw(
                    _leaf_key(base, 1, i, l), shape, std, dtype),
                jnp.arange(d["L"]))
            _set(block, path, stacked)
        tree["stages"] = [{"b0": block}]
        return tree

    return jax.jit(build)(_base_key(seed))


def layer_weights(cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16):
    """One layer's slice of ``make_weights``, drawn alone: the same
    values, key for key."""
    fn = _layer_fn(tuple(sorted(_hashable(cfg).items())), dtype)
    return fn(_base_key(seed), jnp.int32(layer))


def top_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The embedding, final norm and LM head of ``make_weights``, drawn
    alone in one jitted call, keyed by path."""
    leaves = top_leaves(cfg)
    return jax.jit(lambda base: {
        path: _draw(_leaf_key(base, 0, i, 0), shape, std, dtype)
        for i, (path, shape, std) in enumerate(leaves)})(_base_key(seed))


def _hashable(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str, bool, type(None)))}


_LAYER_FNS: dict = {}


def _layer_fn(key, dtype):
    fn = _LAYER_FNS.get((key, dtype))
    if fn is None:
        cfg = dict(key)

        def draw_layer(base, layer):
            tree: dict = {}
            for i, (path, shape, std) in enumerate(layer_leaves(cfg)):
                _set(tree, path, _draw(_leaf_key(base, 1, i, layer), shape,
                                       std, dtype))
            return tree
        fn = _LAYER_FNS[(key, dtype)] = jax.jit(draw_layer)
    return fn


# ---------------------------------------------------------------------------
# forward pass
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``, the
    way an fp8 matmul sees its operands; returned in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, mode: str):
    """a (..., k) @ b (k, n). ``mode`` 'f32': float32 at the highest
    precision; 'fp8': both operands rounded to e4m3 (a per row, b per
    column), accumulated in float32."""
    if mode == "fp8":
        a, b = _fp8(a, -1), _fp8(b, 0)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, pos, theta: float, style: str):
    """x (S, heads, hd); rotate-half RoPE over the full head dim
    ('llama', and 'mrope' with equal section positions) or over its first
    half ('half')."""
    hd = x.shape[-1]
    rot = hd // 2 if style == "half" else hd
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr, 2, axis=-1)
    out = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([out, xp], -1)


def _block(x, w, cfg: dict, mode: str, q_block: int):
    """One decoder layer over one sequence x (S, D), float32."""
    d = dims(cfg)
    S, D, H, KV, hd = x.shape[0], d["D"], d["H"], d["KV"], d["hd"]
    G = H // KV
    eps, theta, style = cfg["norm_eps"], cfg["rope_theta"], cfg["rope_style"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    a = w["attn"]
    h = rms_norm(x, w["ln1"], eps)
    q = _mm(h, a["wq"].reshape(D, H * hd), mode).reshape(S, H, hd)
    k = _mm(h, a["wk"].reshape(D, KV * hd), mode).reshape(S, KV, hd)
    v = _mm(h, a["wv"].reshape(D, KV * hd), mode).reshape(S, KV, hd)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    pos = jnp.arange(S)
    q, k = rope(q, pos, theta, style), rope(k, pos, theta, style)
    # query head j reads KV head j // G
    kh = jnp.repeat(k, G, axis=1).transpose(1, 0, 2)       # (H, S, hd)
    vh = jnp.repeat(v, G, axis=1).transpose(1, 0, 2)
    if mode == "fp8":
        kh, vh = _fp8(kh, -1), _fp8(vh, -2)
    qbs = q.reshape(S // q_block, q_block, H, hd).transpose(0, 2, 1, 3)

    def attend(args):
        qb, s0 = args                                       # (H, Tq, hd)
        if mode == "fp8":
            qb = _fp8(qb, -1)
        s = jnp.einsum("hqd,hkd->hqk", qb, kh,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        qpos = s0 + jnp.arange(q_block)
        s = jnp.where(jnp.arange(S)[None, None, :] <= qpos[None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if mode == "fp8":
            p = _fp8(p, -1)
        return jnp.einsum("hqk,hkd->qhd", p, vh,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(attend, (qbs, jnp.arange(0, S, q_block)))
    o = o.reshape(S, H * hd)
    x = x + _mm(o, a["wo"].reshape(H * hd, D), mode)
    h = rms_norm(x, w["ln2"], eps)
    m = w["mlp"]
    u = jax.nn.silu(_mm(h, m["wi"], mode)) * _mm(h, m["wg"], mode)
    return x + _mm(u, m["wo"], mode)


def served_logits(cfg: dict, seed: int, seqs, at, *, mode: str = "f32",
                  min_len: int = 1024, q_block: int = 512,
                  timings: dict | None = None):
    """Logits of the reference at chosen positions.

    seqs: list of int token arrays (prompt followed by the served tokens);
    at: for each sequence, the positions whose next-token logits are
    wanted. Returns one (len(at[i]), V) float32 numpy array per sequence.
    Sequences are padded at the end to a power of two of at least
    ``min_len`` tokens (causal attention leaves the real positions
    unchanged), so a handful of shapes compile once each and every layer
    reuses them. ``timings`` (if given) receives the seconds of each
    phase."""
    import time
    t0 = time.perf_counter()
    top = jax.tree.map(lambda a: a.astype(jnp.float32),
                       top_weights(cfg, seed))
    eps = cfg["norm_eps"]
    lengths = [len(s) for s in seqs]
    xs = []
    for s in seqs:
        n = max(min_len, 1 << (len(s) - 1).bit_length())
        ids = np.zeros(n, np.int32)
        ids[:len(s)] = s
        xs.append(top["embed"][jnp.asarray(ids)])
    step = jax.jit(lambda x, w: _block(x, w, cfg, mode, q_block))
    t1 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        for layer in range(cfg["num_layers"]):
            w = layer_weights(cfg, seed, layer)
            xs = [step(x, w) for x in xs]
            if layer == 0:
                t_first = time.perf_counter()
        jax.block_until_ready(xs)
        t2 = time.perf_counter()
        # every wanted row of every sequence through the head in one call,
        # padded to a power of two so that few shapes ever compile
        rows = np.concatenate([np.asarray(x)[np.asarray(pos, np.int64)]
                               for x, pos in zip(xs, at)])
        n = rows.shape[0]
        buf = np.zeros((max(256, 1 << (n - 1).bit_length()), rows.shape[1]),
                       np.float32)
        buf[:n] = rows
        tied = bool(cfg.get("tie_embeddings"))
        logits = np.asarray(_head(
            jnp.asarray(buf), top["final_norm"],
            top["embed"] if tied else top["lm_head"], eps=eps, tied=tied,
            mode=mode))[:n]
    if timings is not None:
        timings.update(embed_s=t1 - t0, first_layer_s=t_first - t1,
                       layers_s=t2 - t1, head_s=time.perf_counter() - t2,
                       padded=sorted({int(x.shape[0]) for x in xs}))
    return np.split(logits, np.cumsum([len(p) for p in at])[:-1])


@functools.partial(jax.jit, static_argnames=("eps", "tied", "mode"))
def _head(h, gain, w, *, eps: float, tied: bool, mode: str):
    """Final norm and LM head (the embedding, transposed, where tied)."""
    return _mm(rms_norm(h, gain, eps), w.T if tied else w, mode)
