"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the program finished, drawn from the seed and always holding
the longest, is run through the plain reference: each prompt followed by
the tokens the program served, teacher-forced, in float32. At every
served position the reference's best logit is compared with its logit for
the served token; the widest such gap over the sample must stay under the
configuration's limit. Greedy decoding serves the argmax, so a sound
bf16 program only loses where the reference's top two are within its
rounding, and the gap stays small; a token altered where it is produced,
or attention over the wrong keys, opens it wide.

The control puts the reference in the program's place one precision
below the configuration's (float8 e4m3 operands for bfloat16): at the
same positions it reads the gap of the token the control ranks first.
"""
from __future__ import annotations

import zlib

import numpy as np


def content_chunks(spec) -> list[tuple[str, int]]:
    """A copy of ``Request.content_chunks``: the prompt's segments in
    canonical order, [shared system prefix][mm payload][private text]."""
    chunks, used, total = [], 0, spec.prompt_tokens
    if spec.shared_prefix_tokens > 0 and spec.shared_prefix_id:
        n = min(spec.shared_prefix_tokens, total)
        chunks.append((f"sys:{spec.shared_prefix_id}", n))
        used += n
    if spec.mm_units > 0 and used < total:
        cid = f"mm:{spec.mm_hash}" if spec.mm_hash else f"mm!{spec.rid}"
        n = min(spec.mm_units, total - used)
        chunks.append((cid, n))
        used += n
    if used < total:
        chunks.append((f"txt!{spec.rid}", total - used))
    return chunks


def prompt_ids(spec, vocab: int) -> np.ndarray:
    """The prompt's token ids: each segment a seeded stream keyed by its
    content id (the rid for a private prompt), as the executor's
    ``_prompt_tokens`` defines the served inputs."""
    chunks = content_chunks(spec)
    if len(chunks) == 1 and chunks[0][0] == f"txt!{spec.rid}":
        seed = zlib.crc32(spec.rid.encode()) & 0x7FFFFFFF
        return np.random.default_rng(seed).integers(
            1, vocab, size=spec.prompt_tokens, dtype=np.int64)
    return np.concatenate([
        np.random.default_rng(zlib.crc32(cid.encode()) & 0x7FFFFFFF)
        .integers(1, vocab, size=n, dtype=np.int64) for cid, n in chunks])


def sample(finished: dict, specs: dict, seed: int, *, min_tokens: int,
           max_requests: int) -> list[str]:
    """The longest finished request (prompt plus output), then others in
    an order drawn from the seed until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    rids = sorted(finished)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (specs[r].prompt_tokens
                                       + len(finished[r]), r))
    pick, total = [longest], len(finished[longest])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC0])
    for j in rng.permutation(len(rids)):
        r = rids[j]
        if total >= min_tokens or len(pick) >= max_requests:
            break
        if r != longest:
            pick.append(r)
            total += len(finished[r])
    return pick


def compare(ref, cfg: dict, seed: int, specs: dict, finished: dict,
            rids: list[str], *, control: bool = False) -> dict:
    """Widest gap between the reference's best logit and its logit for the
    served token, over every served token of ``rids``; with ``control``
    also the gap of the token the fp8 control ranks first."""
    seqs, at, served = [], [], []
    short = 0
    for r in rids:
        toks = np.asarray(finished[r], np.int64)
        want = specs[r].output_tokens
        short += abs(len(toks) - want)
        p = prompt_ids(specs[r], cfg["vocab_size"])
        seqs.append(np.concatenate([p, toks[:-1]]))
        at.append(np.arange(len(p) - 1, len(p) - 1 + len(toks)))
        served.append(toks)
    out = {"requests": len(rids), "tokens": int(sum(len(s) for s in served)),
           "tokens_short": int(short)}
    if not rids:
        # nothing finished to compare: no number, and not correct
        out["logit_gap"] = out["control_gap"] = None
        out["mismatched_tokens"] = 0
        return out
    out["timings"] = {}
    logits = ref.served_logits(cfg, seed, seqs, at, mode="f32",
                               timings=out["timings"])
    gaps, ctl_gaps, best = [], [], []
    for lg, toks in zip(logits, served):
        top = lg.max(axis=-1)
        gaps.append(top - np.take_along_axis(lg, toks[:, None], -1)[:, 0])
        best.append(top)
    out["logit_gap"] = float(max(g.max() for g in gaps))
    out["mismatched_tokens"] = int(sum((g > 0).sum() for g in gaps))
    if control:
        c_logits = ref.served_logits(cfg, seed, seqs, at, mode="fp8")
        for lg, c, top in zip(logits, c_logits, best):
            pick = c.argmax(axis=-1)
            ctl_gaps.append(
                top - np.take_along_axis(lg, pick[:, None], -1)[:, 0])
        out["control_gap"] = float(max(g.max() for g in ctl_gaps))
    return out


def judge(res: dict, limit: float, gap: str = "logit_gap") -> tuple:
    """(correct, compared) for one scored sample: at least one request
    compared, its widest gap (``res[gap]``: the program's, or the
    control's) at most ``limit``, and no served request short of or past
    its output length."""
    compared = {"logit_gap": {"value": res[gap], "limit": limit},
                "tokens_short": {"value": res["tokens_short"], "limit": 0}}
    correct = (res["requests"] > 0 and res[gap] is not None
               and res[gap] <= limit and res["tokens_short"] == 0)
    return bool(correct), compared
