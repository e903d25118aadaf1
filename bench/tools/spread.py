"""Run one cell several times, each run a process of its own as the
benchmark's check runs it, and report every metric's spread.

    python3 bench/tools/spread.py --workload <name> --seeds 11,12,13 \
        [--sets 2] [--trace-seeds 21,22,23] [--seconds 10] [--out DIR]

Runs ``bench/run.py`` once per seed in each of ``--sets`` sets (the same
seeds in every set), then once with ``--trace 1`` per trace seed.  Prints
each run's result line, then per set and metric the median and the
spread: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.  Every
result line also goes to ``DIR/<workload>.jsonl`` (default
``chiprun_out``).
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run(workload, seed, seconds, trace):
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)], cwd=ROOT, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        line = {"error": proc.stderr[-2000:]}
    line.update(seed=seed, trace=trace, rc=proc.returncode,
                wall_s=time.perf_counter() - t0)
    if len(lines) > 1 and lines[-2].startswith('{"info"'):
        line["info"] = json.loads(lines[-2])["info"]
    return line


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / med if med else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"))
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    sets = []
    with open(out / f"{args.workload}.jsonl", "a") as log:
        def record(line):
            log.write(json.dumps(line) + "\n")
            log.flush()
            short = {k: line.get(k) for k in ("seed", "trace", "rc",
                                              "correct", "wall_s")}
            short["metrics"] = {k: v["value"] for k, v in
                                line.get("metrics", {}).items()}
            short["checks"] = {k: v["value"] for k, v in
                               line.get("checks", {}).items()}
            print(json.dumps(short), flush=True)
            return line

        for _ in range(args.sets if seeds else 0):
            sets.append([record(run(args.workload, s, args.seconds, 0))
                         for s in seeds])
        for s in (int(s) for s in args.trace_seeds.split(",") if s):
            record(run(args.workload, s, args.seconds, 1))
    for i, runs in enumerate(sets):
        names = sorted({k for r in runs for k in r.get("metrics", {})})
        for name in names:
            vals = [r["metrics"][name]["value"] for r in runs
                    if name in r.get("metrics", {})]
            if len(vals) >= 2:
                med, sp = spread(vals)
                print(json.dumps({"set": i, "metric": name, "n": len(vals),
                                  "median": med, "spread": sp}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
