"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix, driver and metric readers are found
by name from ``BENCHMARK.json`` (see ``bench/lib/manifest.py``).  The run
refuses, printing no result, unless JAX's first device is a TPU and there
are as many as the cell asks for.  Set-up (imports, device start, state,
warm-up, compilation) is timed as ``setup_s``; then the driver measures
for ``--seconds``.  With ``--trace 0`` the result's metrics are the cell's
end-to-end ones; with ``--trace 1`` the run also records the program's
spans and a profiler trace of the window's first seconds, and the metrics
are the cell's per-layer ones.  After the window the driver compares what
the timed path produced with the plain reference; each number compared is
printed beside its limit on the last lines of standard error and under
``checks``, the last key of the result line, which is the last line of
standard output.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where the machine sets it, else ``.jax_cache`` at the checkout's root
    (a fixed path: the path is part of the cache's key).  Every program
    is cached, however quickly it compiled."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def run_cell(root, workload, *, seed, seconds, trace, devices, t0):
    """Drive one cell and reduce it to the result line's fields."""
    from bench.lib import compile_meter, manifest, xplane

    cell = manifest.cell(root, workload)
    drv = manifest.driver(root, cell["traffic"]["driver"])
    meter = compile_meter.CompileMeter()
    out = drv.run(cell, seed=seed, seconds=seconds, trace=trace, t0=t0,
                  devices=devices, meter=meter)
    units = {m["name"]: m["unit"] for m in
             cell["end_to_end"] + cell["per_layer"]}
    if trace:
        metrics = {}
        for m in cell["per_layer"]:
            v = manifest.reader(root, m["name"])(out["ctx"])
            if v is not None:
                metrics[m["name"]] = v
    else:
        metrics = {**out["e2e"], "setup_s": out["setup_s"]}
        metrics = {m["name"]: metrics[m["name"]]
                   for m in cell["end_to_end"]}
    used = devices[:cell["workload"]["chips"]]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": all(c["value"] <= c["limit"]
                           for c in out["checks"].values()),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": {k: {"value": float(v), "unit": units[k]}
                        for k, v in metrics.items()},
            "device": device}
    if trace:
        red = out["ctx"]["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": xplane.top(red["ops"]),
                             "idle_gaps": xplane.top(red["idle_by_host"])}
    line["checks"] = out["checks"]
    return line, out["info"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import manifest

    try:
        chips = manifest.cell(ROOT, args.workload)["workload"]["chips"]
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    enable_compile_cache()
    line, info = run_cell(ROOT, args.workload, seed=args.seed,
                          seconds=args.seconds, trace=args.trace,
                          devices=devices, t0=T0)
    print(json.dumps({"info": info}), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
