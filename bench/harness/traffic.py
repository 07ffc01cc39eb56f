"""Seeded open-loop traffic, read from data files.

A *mix* (``bench/mixes/<name>.json``) holds the generator's parameters; a
*traffic* (``bench/traffic/<name>.json``) names a mix and the rate it is
offered at. ``generate`` is a copy of ``repro.serving.workload.generate``
(paper §4.1: modality mix, lognormal text and output lengths, near-constant
image patches, frames x patches for video, plus the trace-shaped knobs:
Pareto tails, diurnal and burst shaping, zipf tenants with shared system
prompts), kept here so that no change to the program can move the
yardstick. ``window_requests`` turns it into one run's arrivals.

Every seed serves one schedule, its request sizes and inter-arrival
spacings drawn from the mix's ``base_seed`` and laid on a circle of the
window's length; ``--seed`` draws where on that circle the window opens,
always in one of its longest gaps (and names the requests, which seeds
their prompt tokens). So runs on different seeds do the same work in
another order, and none cuts a cluster of arrivals in two.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, replace
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]

# the generator's parameters and their defaults (WorkloadConfig's)
DEFAULTS = {
    "mix": {"text": 0.50, "image": 0.30, "video": 0.20},
    "text_tokens_log_mu": 5.3,
    "text_tokens_log_sigma": 1.3,
    "text_tokens_min": 10,
    "text_tokens_max": 10000,
    "mm_text_log_mu": 3.6,
    "mm_text_log_sigma": 0.6,
    "mm_text_min": 8,
    "mm_text_max": 256,
    "image_patches": 576,
    "image_patch_jitter": 0.15,
    "video_frames_min": 8,
    "video_frames_max": 64,
    "video_patches_per_frame": 196,
    "out_tokens_log_mu": 4.2,
    "out_tokens_log_sigma": 0.8,
    "out_tokens_min": 4,
    "out_tokens_max": 1024,
    "duplicate_prob": 0.0,
    "shared_prefix_prob": 0.0,
    "shared_prefix_pool": 4,
    "shared_prefix_tokens_min": 64,
    "shared_prefix_tokens_max": 256,
    "heavy_tail_prob": 0.0,
    "heavy_tail_alpha": 1.6,
    "heavy_tail_text_cap": 32768,
    "heavy_tail_out_cap": 4096,
    "diurnal_amplitude": 0.0,
    "diurnal_period_s": 600.0,
    "burst_prob": 0.0,
    "burst_factor": 4.0,
    "burst_len_s": 5.0,
    "tenants": 0,
    "tenant_zipf_a": 1.2,
    "tenant_sys_prob": 0.75,
    "tenant_sys_tokens_min": 64,
    "tenant_sys_tokens_max": 256,
    # prompt + output tokens of one request stay within this many: the
    # served context window less a margin (0 = no cap)
    "context_cap": 0,
    # seed of the schedule every run shares
    "base_seed": 0,
    # the window opens in one of the schedule's this many longest gaps
    "phases": 8,
}


@dataclass(frozen=True)
class Spec:
    """One request as the client sends it; ``arrival`` is its scheduled
    send time in seconds after the window opens."""
    rid: str
    modality: str
    arrival: float
    text_tokens: int
    mm_units: int
    output_tokens: int
    mm_hash: str | None = None
    shared_prefix_id: str | None = None
    shared_prefix_tokens: int = 0
    tenant: str = "default"

    @property
    def prompt_tokens(self) -> int:
        return self.text_tokens + self.mm_units


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    """{'name', 'mix', 'rate_rps', 'rows', 'params'} for
    ``traffic/<name>.json``: ``rows`` bounds the requests one step of the
    served model holds at this rate ({'prefill': n, 'decode': n}), and
    set-up compiles every step signature within it."""
    t = load_json(bench_dir / "traffic" / f"{name}.json")
    mix = load_json(bench_dir / "mixes" / f"{t['mix']}.json")
    params = dict(DEFAULTS)
    unknown = set(mix.get("params", {})) - set(DEFAULTS)
    if unknown:
        raise ValueError(f"mix {t['mix']}: unknown parameters {sorted(unknown)}")
    params.update(mix.get("params", {}))
    return {"name": name, "mix": t["mix"], "rate_rps": float(t["rate_rps"]),
            "rows": dict(t["rows"]), "params": params}


def _shape_arrivals(p: dict, gaps: np.ndarray,
                    trng: np.random.Generator) -> np.ndarray:
    """Diurnal and burst shaping: each gap is rescaled by the
    instantaneous rate multiplier (a thinned inhomogeneous Poisson
    process); only burst starts draw from ``trng``."""
    t = 0.0
    burst_until = -1.0
    shaped = np.empty(len(gaps))
    for i, g in enumerate(gaps):
        mult = 1.0
        if p["diurnal_amplitude"] > 0:
            mult *= max(0.05, 1.0 + p["diurnal_amplitude"] *
                        np.sin(2.0 * np.pi * t / p["diurnal_period_s"]))
        if t < burst_until:
            mult *= p["burst_factor"]
        t += g / mult
        shaped[i] = t
        if p["burst_prob"] > 0 and t >= burst_until and \
                trng.uniform() < p["burst_prob"]:
            burst_until = t + p["burst_len_s"]
    return shaped


def _tenant_pool(p: dict, seed: int, trng):
    if p["tenants"] <= 0:
        return [], None
    mix = p["mix"]
    base = np.array([mix["text"], mix["image"], mix["video"]])
    specs = []
    for k in range(p["tenants"]):
        f = k / max(1, p["tenants"] - 1)
        lean = (np.array([0.90, 0.08, 0.02]) * (1 - f)
                + np.array([0.25, 0.25, 0.50]) * f)
        w = 0.5 * base + 0.5 * lean
        w = w / w.sum()
        sys_toks = int(trng.integers(p["tenant_sys_tokens_min"],
                                     p["tenant_sys_tokens_max"] + 1))
        specs.append((f"tenant{k}", w, f"t{seed}-{k}", sys_toks))
    ranks = np.arange(1, p["tenants"] + 1, dtype=float)
    pop = ranks ** -p["tenant_zipf_a"]
    return specs, pop / pop.sum()


def generate(p: dict, num_requests: int, rate: float, seed: int) -> list[Spec]:
    """The program's generator, draw for draw (``rid`` ``r<i>``, arrivals
    from Poisson gaps at ``rate``)."""
    rng = np.random.default_rng(seed)
    mix = p["mix"]
    modalities = rng.choice(["text", "image", "video"], size=num_requests,
                            p=[mix["text"], mix["image"], mix["video"]])
    gaps = rng.exponential(1.0 / rate, size=num_requests)
    arrivals = np.cumsum(gaps)
    trace_on = (p["heavy_tail_prob"] > 0 or p["diurnal_amplitude"] > 0
                or p["burst_prob"] > 0 or p["tenants"] > 0)
    trng = np.random.default_rng(seed + 0x7ACE) if trace_on else None
    if p["diurnal_amplitude"] > 0 or p["burst_prob"] > 0:
        arrivals = _shape_arrivals(p, gaps, trng)
    tenant_specs, tenant_pop = _tenant_pool(p, seed, trng)
    pools: dict[str, list[tuple[str, int]]] = {"image": [], "video": []}
    sys_pool: list[tuple[str, int]] = []
    if p["shared_prefix_prob"] > 0:
        prng = np.random.default_rng(seed + 0x5F5)
        sys_pool = [
            (f"s{seed}-{j}",
             int(prng.integers(p["shared_prefix_tokens_min"],
                               p["shared_prefix_tokens_max"] + 1)))
            for j in range(p["shared_prefix_pool"])]
    out = []
    for i, (mod, t) in enumerate(zip(modalities, arrivals)):
        mod = str(mod)
        tenant = "default"
        shared_id, shared_toks = None, 0
        if tenant_specs:
            k = int(trng.choice(len(tenant_specs), p=tenant_pop))
            tenant, tmix, sys_id, sys_toks = tenant_specs[k]
            mod = str(trng.choice(["text", "image", "video"], p=tmix))
            if trng.uniform() < p["tenant_sys_prob"]:
                shared_id, shared_toks = sys_id, sys_toks
        out_toks = int(np.clip(rng.lognormal(
            p["out_tokens_log_mu"], p["out_tokens_log_sigma"]),
            p["out_tokens_min"], p["out_tokens_max"]))
        if p["heavy_tail_prob"] > 0 and trng.uniform() < p["heavy_tail_prob"]:
            out_toks = min(p["heavy_tail_out_cap"],
                           int(32 * (1 + trng.pareto(p["heavy_tail_alpha"]))))
        mm_hash = None
        if mod == "text":
            text = int(np.clip(rng.lognormal(
                p["text_tokens_log_mu"], p["text_tokens_log_sigma"]),
                p["text_tokens_min"], p["text_tokens_max"]))
            mm = 0
            if p["heavy_tail_prob"] > 0 and \
                    trng.uniform() < p["heavy_tail_prob"]:
                text = min(p["heavy_tail_text_cap"],
                           int(200 * (1 + trng.pareto(p["heavy_tail_alpha"]))))
            if shared_id is None and sys_pool and \
                    rng.uniform() < p["shared_prefix_prob"]:
                shared_id, shared_toks = \
                    sys_pool[int(rng.integers(len(sys_pool)))]
        else:
            text = int(np.clip(rng.lognormal(
                p["mm_text_log_mu"], p["mm_text_log_sigma"]),
                p["mm_text_min"], p["mm_text_max"]))
            if p["duplicate_prob"] > 0 and pools[mod] and \
                    rng.uniform() < p["duplicate_prob"]:
                mm_hash, mm = pools[mod][int(rng.integers(len(pools[mod])))]
            else:
                if mod == "image":
                    mm = int(p["image_patches"] *
                             (1 + rng.uniform(-p["image_patch_jitter"],
                                              p["image_patch_jitter"])))
                else:
                    frames = int(rng.integers(p["video_frames_min"],
                                              p["video_frames_max"] + 1))
                    mm = frames * p["video_patches_per_frame"]
                mm_hash = f"{mod}-{i:05d}"
                pools[mod].append((mm_hash, mm))
        if shared_id is not None:
            text += shared_toks
        out.append(Spec(
            rid=f"r{i:05d}", modality=mod, arrival=float(t), text_tokens=text,
            mm_units=mm, output_tokens=out_toks, mm_hash=mm_hash,
            shared_prefix_id=shared_id, shared_prefix_tokens=shared_toks,
            tenant=tenant))
    return out


def _capped(s: Spec, cap: int, min_out: int) -> Spec:
    """Fit one request into ``cap`` tokens of context: long text is cut
    first (a prompt keeps room for ``min_out`` output tokens), then the
    output. Multimodal payloads are never cut; a mix whose payload cannot
    fit is refused."""
    if cap <= 0:
        return s
    text = s.text_tokens
    if s.mm_units + text > cap - min_out:
        text = cap - min_out - s.mm_units
        if text < max(1, s.shared_prefix_tokens):
            raise ValueError(f"{s.rid}: a {s.mm_units}-unit payload does not "
                             f"fit the {cap}-token context cap")
    out = min(s.output_tokens, cap - s.mm_units - text)
    return replace(s, text_tokens=text, output_tokens=out)


def window_requests(traffic: dict, seconds: float, seed: int,
                    prefix: str = "") -> list[Spec]:
    """The requests one run sends in a window of ``seconds``.

    The schedule: round(rate x seconds) requests, sizes and spacings drawn
    from the mix's base seed, the spacings normalised to fill a circle of
    ``seconds`` (a Poisson process conditioned on N arrivals, burst and
    diurnal shaping applied to them). ``seed`` picks one of the circle's
    ``phases`` longest gaps, and the window opens halfway into it."""
    p = traffic["params"]
    rate = traffic["rate_rps"]
    n = max(1, int(round(rate * seconds)))
    base = generate(p, n, rate, p["base_seed"])
    spacings = np.random.default_rng(p["base_seed"] + 1).exponential(
        1.0, n + 1)
    spacings = spacings / spacings.sum() * seconds
    if p["diurnal_amplitude"] > 0 or p["burst_prob"] > 0:
        points = _shape_arrivals(p, spacings, np.random.default_rng(
            [p["base_seed"], 0x7ACE]))
    else:
        points = np.cumsum(spacings)
    arrivals, circle = points[:n], points[n]
    # the gap before each arrival, the first one's closing the circle
    before = np.diff(arrivals, prepend=arrivals[-1] - circle)
    quiet = np.argsort(-before, kind="stable")[:max(1, int(p["phases"]))]
    first = quiet[np.random.default_rng(seed).integers(len(quiet))]
    arrivals = (arrivals - arrivals[first] + before[first] / 2) % circle
    cap = int(p["context_cap"])
    tag = f"{prefix}s{seed}"
    out = []
    for slot, j in enumerate(np.argsort(arrivals, kind="stable")):
        s = _capped(base[j], cap, int(p["out_tokens_min"]))
        mm_hash = f"{s.mm_hash}-{tag}" if s.mm_hash else None
        out.append(replace(s, rid=f"{tag}-{slot:05d}",
                           arrival=float(arrivals[j]), mm_hash=mm_hash))
    return [s for s in out if s.arrival < seconds]
