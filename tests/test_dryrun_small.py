"""Dry-run machinery regression: lower+compile+analyze a small arch on an
8-device placeholder mesh (subprocess: the XLA device flag must precede jax
init).  Covers mesh building, sharding rules, step builders, HLO analyzer."""
import json
import subprocess
import sys

SCRIPT = r'''
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, "src")
import jax, json
from repro.launch.mesh import auto_mesh
from repro import configs as cfgs
from repro.launch import steps as S
from repro.launch import hloanalysis as H
from repro.models.config import SHAPES
mesh = auto_mesh((4, 2), ("data", "model"))
for arch, si in (("smollm-360m", 0), ("mamba2-130m", 3)):
    cfg = cfgs.get(arch)
    cell = SHAPES[si]
    fn, args, insh, outsh, don = S.build_cell(cfg, cell, mesh)
    compiled = jax.jit(fn, in_shardings=insh, out_shardings=outsh,
                       donate_argnums=don).lower(*args).compile()
    c = H.analyze(compiled.as_text(), 8)
    assert c.flops > 0, (arch, "no flops found")
    assert c.hbm_bytes > 0
    assert c.trips, "scan trip counts missing"
    print(json.dumps({"arch": arch, "flops": c.flops,
                      "trips": max(c.trips.values())}))
print("DRYRUN_SMALL_OK")
'''


def test_dryrun_small_mesh():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=560, cwd=".")
    assert "DRYRUN_SMALL_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
    rows = [json.loads(l) for l in r.stdout.splitlines() if l.startswith("{")]
    smollm = next(x for x in rows if x["arch"] == "smollm-360m")
    # layer-scan trip count must be visible to the analyzer (32 layers)
    assert smollm["trips"] >= 32
    # flops must be in the analytic ballpark: ~8*N*D/8dev for fwd+bwd+remat
    assert 1e13 < smollm["flops"] < 5e15
