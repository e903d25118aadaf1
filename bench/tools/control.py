"""The control of a cell's ``correct``: the plain reference with one
stated guarantee broken, put in the program's place, then compared with
the plain reference by the cell's own check.

  * serving cells: ``NoWriteThroughKV`` (posted writes never reach the
    TSU), driven exactly as ``bench/run.py`` drives the program (set-up,
    warm-up, the window's replay);
  * the engine cell: the reference simulator with HALCONE's fences
    leaving every clock where it was, over the cell's traces.

    python3 bench/tools/control.py --workload <name> --seeds 1,2,3 \
        [--seconds 10]

Prints one JSON line per seed with each compared number; every seed must
read above the numbers' limits.  Runs on the host alone (no device).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class RefBackend:
    """A reference object behind the fabric calls the replay makes."""

    def __init__(self, kv):
        self.kv = kv

    def write_batch(self, items, replica=0):
        self.kv.write_batch(items, replica)

    def fence(self):
        return self.kv.fence()

    def read_batch(self, keys, replica=0):
        return self.kv.read_batch(keys, replica)

    def read_batch_async(self, keys, replica=0):
        out = self.kv.read_batch(keys, replica)

        class Done:
            def result(self):
                return out
        return Done()

    def stats(self):
        return self.kv.stats()


def sweep_control(drv, config, traffic, seed):
    import numpy as np

    from bench.refs import halcone_engine as he

    ops, addrs = drv.make_traces(config, traffic, seed)
    want = drv.reference_counters(config, ops, addrs)
    got = np.stack([np.stack([o[k] for k in he.COUNTERS], -1) for o in (
        he.simulate(s, ops, addrs, fence_raises_clocks=False)
        for s in drv.systems(config))])
    return {"counter_mismatches": int((got != want).sum())}, got.size


def control_checks(root, workload, seed, seconds):
    """The checks ``bench/run.py`` would print with the control in the
    program's place, and how many answers they compared."""
    from bench.lib import manifest
    from bench.refs.lease_kv import NoWriteThroughKV

    cell = manifest.cell(root, workload)
    drv = manifest.driver(root, cell["traffic"]["driver"])
    config, traffic = cell["config"], cell["traffic"]
    if cell["traffic"]["driver"] == "sweep":
        return sweep_control(drv, config, traffic, seed)
    kv = NoWriteThroughKV(n_nodes=config["n_nodes"],
                          replicas_per_node=config["replicas_per_node"],
                          **{k: config[k] for k in drv.FABRIC_KEYS})
    backend = RefBackend(kv)
    proxy = drv.Proxy(backend)
    drv.warm_up(proxy, config, traffic, seed)
    for tr in drv.window_traces(traffic, seed, seconds):
        drv._replay(proxy, tr, config, traffic)
    checks, compared = drv.check(config, proxy.calls, backend.stats())
    return checks, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for seed in (int(s) for s in args.seeds.split(",")):
        checks, compared = control_checks(ROOT, args.workload, seed,
                                          args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "compared": compared, **checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
