"""Readings for the limit that decides ``correct``: the program's widest
logit gap and the control's, seed by seed, in one process.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 30

The stack is built and warmed once; each seed then serves its own weights
through a window of the cell's own traffic, and the plain reference scores
the sample a run scores, once in float32 (the program's reading) and once
with float8 e4m3 operands (the control: one precision below the
configuration's bfloat16, put in the program's place). Both readings go
through the run's own test (``check.judge``) against the configuration's
limit. Prints one JSON line per seed; the benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import json

import run
from harness import check


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    c = run.Cell(args.workload)
    c.build(seeds[0])
    c.warm()
    for i, seed in enumerate(seeds):
        if i:
            c.reseed(seed)
        w = c.window(seed, args.seconds, trace=False)
        del w["engine"]
        res = c.score(seed, w["rec"], control=True)
        program_ok, _ = check.judge(res, c.limit)
        control_ok, _ = check.judge(res, c.limit, gap="control_gap")
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "limit": c.limit,
                           "logit_gap": res["logit_gap"],
                           "control_gap": res["control_gap"],
                           "program_correct": program_ok,
                           "control_correct": control_ok,
                           "requests": res["requests"],
                           "tokens": res["tokens"],
                           "mismatched_tokens": res["mismatched_tokens"],
                           "tokens_short": res["tokens_short"],
                           "reference_s": res["seconds"]})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
