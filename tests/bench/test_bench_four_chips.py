"""A four-chip serving cell rehearsed on four virtual CPU devices (in a
child process, since the device count is fixed when JAX starts): the
lease configuration on ``ShardedArrayFabric``, its TSU shards over four
chips, serving the open-loop storms mix, added as new files and manifest
entries the way a later change adds the cell.  Its results equal the
plain reference's, and leaving out the exchange that brings the TSU rows
back to their owning chips makes ``correct`` false."""
import json
import os
import subprocess
import sys

from conftest import ROOT

SCRIPT = r"""
import json, sys, tempfile, time
from pathlib import Path
sys.path[:0] = [%(root)r, %(root)r + "/src", %(root)r + "/tests/bench"]
import jax
from conftest import add_storms_cell, make_tiny_root
from bench.run import run_cell
from repro.coherence.fabric import ShardedArrayFabric

root = make_tiny_root(Path(tempfile.mkdtemp()))
conf = json.loads((root / "bench/configs/lease-kv-8x16384.json").read_text())
conf.update(name="lease-kv-4chip", backend="ShardedArrayFabric", chips=4)
(root / "bench/configs/lease-kv-4chip.json").write_text(json.dumps(conf))
man = json.loads((root / "BENCHMARK.json").read_text())
man["configs"].append({"name": "lease-kv-4chip", "source": "test",
                       "file": "bench/configs/lease-kv-4chip.json",
                       "reduced": [], "why": "test"})
(root / "BENCHMARK.json").write_text(json.dumps(man))
cell = add_storms_cell(root, name="miss.4chip", config="lease-kv-4chip",
                       mix="miss4", chips=4, max_batch=32,
                       warmup_reads=128)    # fewer programs on four devices
kw = dict(seed=2 ** 31 + 3, seconds=0.3, trace=0, devices=jax.devices())
out = {"devices": len(jax.devices())}
line, info = run_cell(root, cell, t0=time.perf_counter(), **kw)
out["sound"] = [line["correct"], line["device"]["count"], info["storms"]]

def no_exchange(self, af):
    # keep the pass's other state but not the TSU rows it updated
    self._af = af._replace(tsu=self._af.tsu, tsu_ver=self._af.tsu_ver,
                           tsu_gseq=self._af.tsu_gseq,
                           tsu_seq=self._af.tsu_seq,
                           tsu_nseq=self._af.tsu_nseq)
    self._dispatch_gather()

ShardedArrayFabric._xout = no_exchange
line, _ = run_cell(root, cell, t0=time.perf_counter(), **kw)
out["broken"] = [line["correct"], line["checks"]]
print(json.dumps(out))
"""


def test_four_chip_cell_and_missing_exchange():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", SCRIPT % {"root": str(ROOT)}],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["sound"][0] is True and out["sound"][1] == 4
    assert out["sound"][2] > 0
    assert out["broken"][0] is False
