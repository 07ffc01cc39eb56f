"""Knee sweep: the cell's traffic offered at several fixed rates, on
several seeds each, one window each, in one process on the chip.

  python3 bench/sweep.py --workload <cell> --rates 0.5,1,2 --seeds 1,2 \
      --seconds 51 [--rows 8,32]

Set-up is a run's (the stack, then every step signature within the
traffic's row bounds, or within ``--rows`` prefill,decode for rates above
the cell's). For each window it prints one JSON line: the tail metrics,
output tokens per second, the backlog (requests sent and not finished) at
the window's middle and end with its growth per second, and the jit
signatures compiled in the window. The knee is the highest rate whose
backlog does not grow through the window; the cells then offer fixed
rates below and above it (their traffic files).
"""
from __future__ import annotations

import argparse
import json

import run
from harness.stats import percentile


def backlog(rec, t: float) -> int:
    return sum(1 for rid, (sched, _sent) in rec.sends.items()
               if sched <= t and rec.ended.get(rid, float("inf")) > t)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seeds", default="1", help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rows", default=None,
                    help="prefill,decode row bounds to warm")
    ap.add_argument("--drain", type=float, default=20.0)
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    run.DRAIN_S = args.drain
    seeds = [int(s) for s in args.seeds.split(",")]
    c = run.Cell(args.workload)
    if args.rows:
        p, d = (int(x) for x in args.rows.split(","))
        c.traffic["rows"] = {"prefill": p, "decode": d}
    c.e2e_names = ["ttft_p90_s", "sand_ttft_p50_s", "itl_p99_ms",
                   "output_tok_s"]
    c.build(seeds[0])
    c.warm()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        c.traffic["rate_rps"] = rate
        for seed in seeds:
            # a seed per window: request names (and so the executor's
            # per-request memos) never repeat within the process
            w = c.window(seed + 7919 * i, args.seconds, trace=False)
            del w["engine"]
            rec, end = w["rec"], w["phase"]["window_end"]
            mid_b, end_b = backlog(rec, end / 2), backlog(rec, end)
            steps = [s for s in rec.steps if s["t1"] <= end]
            line = dict(rate_rps=rate, seed=seed + 7919 * i, **w["e2e"],
                        ttft_p50_s=w["counts"]["ttft_p50_s"],
                        missing_first_token=w["counts"]["missing_first_token"],
                        sent=w["counts"]["sent"], backlog_mid=mid_b,
                        backlog_end=end_b,
                        backlog_growth_per_s=(end_b - mid_b) / (end / 2),
                        decode_rows_p50=percentile(
                            [len(s["decode"]) for s in steps], 50),
                        decode_rows_max=max(
                            (len(s["decode"]) for s in steps), default=0),
                        prefill_rows_max=max(
                            (len(s["prefill"][0]) for s in steps
                             if s["prefill"]), default=0),
                        new_signatures=w["new_signatures"])
            print(json.dumps(line), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
