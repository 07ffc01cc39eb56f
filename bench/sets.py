"""Runs of one cell in sets, each run its own process, and the spread of
each metric: what a benchmark's bounds are set from.

  python3 bench/sets.py --workload <cell> --seeds 1,2,3,4,5,6 --sets 2 \
      --seconds 51 [--trace-seeds 7,8,9] [--out runs.jsonl]

Runs ``bench/run.py`` once per seed, ``--sets`` times over the same
seeds, one process after another (one process holds the chip), then once
with ``--trace 1`` per trace seed. Appends each run's result line to
``--out`` and prints, per set and metric, the median and the spread: the
distance between the first and third quartiles (``statistics.quantiles``,
n=4) as a share of the median, over all runs and without the run
farthest from the median.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def spread(values: list[float]) -> float | None:
    if len(values) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def without_farthest(values: list[float]) -> list[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def one_run(cell: str, seed: int, seconds: str, trace: int) -> dict:
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload", cell,
           "--seed", str(seed), "--seconds", seconds, "--trace", str(trace)]
    p = subprocess.run(cmd, cwd=BENCH.parent, capture_output=True,
                       text=True)
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        result = None
    return {"seed": seed, "trace": trace, "rc": p.returncode,
            "result": result, "stdout_tail": p.stdout[-3000:],
            "stderr_tail": p.stderr[-2000:]}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace-seeds", default="",
                    help="comma-separated seeds of --trace 1 runs")
    ap.add_argument("--out", default=None, help="also append runs here")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    plan = [(k, s, 0) for k in range(args.sets) for s in seeds]
    plan += [(None, int(s), 1) for s in args.trace_seeds.split(",") if s]
    sets: dict = {}
    for k, seed, trace in plan:
        run = one_run(args.workload, seed, args.seconds, trace)
        run["set"] = k
        res = run["result"] or {}
        print(json.dumps({"set": k, "seed": seed, "trace": trace,
                          "rc": run["rc"], "correct": res.get("correct"),
                          "metrics": {m: v["value"] for m, v in
                                      res.get("metrics", {}).items()},
                          "compared": res.get("compared")}), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(run) + "\n")
        if k is not None and res:
            for m, v in res["metrics"].items():
                sets.setdefault(k, {}).setdefault(m, []).append(v["value"])
    for k, metrics in sets.items():
        for m, vals in metrics.items():
            print(json.dumps({
                "set": k, "metric": m, "n": len(vals),
                "median": statistics.median(vals), "spread": spread(vals),
                "spread_without_farthest":
                    spread(without_farthest(vals)) if len(vals) > 2
                    else None,
                "values": vals}), flush=True)


if __name__ == "__main__":
    main()
