"""The lead device's idle time in a traced run of one cell, split by the
innermost program span or harness annotation open at each instant
(``bench/lib/host_spans.py``).

    python3 bench/tools/idle_split.py --workload kv-ycsbc-96.hit \
        --seed 7 [--seconds 10] [--top 12]

Runs the cell as ``bench/run.py --trace 1`` does and splits the profiler
file its driver reduces, read at the moment the driver reads it.  Prints
one JSON line: the result line's ``correct``, metrics and idle gaps, the
split's largest entries, its ``unspanned`` share of the window, and the
split's sum beside the device's idle seconds (window less busy time),
which it equals on one chip.  Needs a TPU and the chips the cell asks
for.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def traced_split(root, workload, *, seed, seconds, devices):
    """``run_cell(..., trace=1)`` of ``bench/run.py``, with the split of
    every profiler file the driver loads (one per traced run)."""
    from bench.lib import host_spans, xplane
    from bench.run import run_cell

    splits = []
    load = xplane.load

    def load_and_split(path):
        out = load(path)
        splits.append(host_spans.split(out[0], host_spans.load(path)))
        return out

    xplane.load = load_and_split
    try:
        line, info = run_cell(root, workload, seed=seed, seconds=seconds,
                              trace=1, devices=devices,
                              t0=time.perf_counter())
    finally:
        xplane.load = load
    return line, info, splits[0]


def summary(line, split, top=12):
    """The printed line: the split beside the window it divides."""
    from bench.lib import host_spans, xplane

    window, busy = line["device"]["window_s"], line["device"]["busy_s"]
    return {"correct": line["correct"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "window_s": window, "busy_s": busy,
            "idle_gaps": line["breakdown"]["idle_gaps"],
            "idle_by_span": xplane.top(split, top),
            "unspanned_pct": (100.0 * split.get(host_spans.UNSPANNED, 0.0)
                              / window) if window > 0 else None,
            "split_sum_s": sum(split.values()), "idle_s": window - busy}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.run import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"idle_split: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    line, _, split = traced_split(ROOT, args.workload, seed=args.seed,
                                  seconds=args.seconds, devices=devices)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      **summary(line, split, args.top)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
