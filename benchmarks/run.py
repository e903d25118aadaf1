"""Benchmark harness: one module per paper table/figure.

Usage:
  PYTHONPATH=src python -m benchmarks.run [--force] [--only fig7,...]
  PYTHONPATH=src python -m benchmarks.run --suite figures [--mini]

``--suite figures`` drives the figure scripts (fig7/8/9 + the Fig-10
per-link traffic decomposition) through the batched sweep engine (one jit
per grid, DESIGN.md §5) and writes one consolidated artifact
``benchmarks/artifacts/figures.json`` (``figures_mini.json`` with
``--mini`` — the CI footprint: 2 configs x 2 benchmarks, small ROUNDS;
mini keeps fig7 + fig10).

The ``fabric`` suite additionally writes the ROOT-LEVEL perf-trajectory
file ``BENCH_fabric.json`` (batched-vs-host serving ops/sec + lease-sweep
wall-clock; DESIGN.md §7) — ``--mini`` shrinks its op counts to the CI
footprint.  The ``replay`` suite writes ``BENCH_serving.json`` (open-loop
offered-load sweep: continuous vs fixed batch formation, p50/p95/p99 +
SLO goodput + the Fig-10 byte decomposition of the replayed traffic;
DESIGN.md §13).

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""
import argparse
import json
import sys
import traceback

from benchmarks.common import ART
from repro.launch.compile_cache import enable_compile_cache


def run_figures(force: bool, mini: bool) -> None:
    """The figure suite on the batched sweep engine + consolidated JSON."""
    from benchmarks import (fig7_speedup, fig8_scaling, fig9_xtreme,
                            fig10_traffic)

    consolidated = {"mini": mini}
    consolidated["fig7"] = fig7_speedup.main(force=force, mini=mini)
    consolidated["fig10"] = fig10_traffic.main(force=force, mini=mini)
    if not mini:
        consolidated["fig8"] = fig8_scaling.main(force=force)
        consolidated["fig9"] = fig9_xtreme.main(force=force)
    out = ART / ("figures_mini.json" if mini else "figures.json")
    out.write_text(json.dumps(consolidated, indent=1))
    print(f"figures artifact: {out}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true",
                    help="recompute instead of using cached artifacts")
    ap.add_argument("--only", default="",
                    help="comma-separated subset (fig2,fig7,fig8,fig9,"
                         "fig10,lease,kernels,roofline,fabric,replay)")
    ap.add_argument("--suite", default="", choices=["", "figures"],
                    help="figures: fig7+fig8+fig9 via the batched sweep "
                         "engine, consolidated into one JSON artifact")
    ap.add_argument("--mini", action="store_true",
                    help="CI footprint: --suite figures runs 2 configs x "
                         "2 benchmarks with small ROUNDS; the fabric suite "
                         "shrinks its op counts")
    args = ap.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived")
    if args.suite == "figures":
        run_figures(args.force, args.mini)
        return

    only = set(args.only.split(",")) if args.only else None
    import functools

    from benchmarks import (fabric_bench, fig2_rdma_gap, fig7_speedup,
                            fig8_scaling, fig9_xtreme, fig10_traffic,
                            kernel_bench, lease_sensitivity, replay_bench,
                            roofline)
    suites = [
        ("fig2", fig2_rdma_gap.main),
        ("fig7", fig7_speedup.main),
        ("fig8", fig8_scaling.main),
        ("fig9", fig9_xtreme.main),
        ("fig10", functools.partial(fig10_traffic.main, mini=args.mini)),
        ("lease", lease_sensitivity.main),
        ("kernels", kernel_bench.main),
        ("roofline", roofline.main),
        ("fabric", functools.partial(fabric_bench.run, mini=args.mini)),
        ("replay", functools.partial(replay_bench.run, mini=args.mini)),
    ]
    failed = []
    for name, fn in suites:
        if only and name not in only:
            continue
        try:
            fn(force=args.force)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
