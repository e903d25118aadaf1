"""chip_smoke.py's phases at tiny sizes on the CPU (the kernels in
interpret mode), and its refusal to run anywhere but on a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

TINY_FABRIC = dict(n_shards=8, tsu_capacity=64, replica_sets=16,
                   replica_ways=2, shared_sets=32, shared_ways=4)
TINY_TRAFFIC = dict(n_reads=384, n_keys=256, zipf_a=0.99, max_batch=64,
                    storm_every=128, storm_n=24)


def test_kernel_parity_phase_tiny():
    out = cs.phase_kernels(lanes=64, write_lanes=16, tsu_capacity=32,
                           cus=16)
    assert out == {"kernel_calls_checked": 7}


def test_fabric_phase_matches_host_fabric_tiny():
    """The served stream, applied to HostFabric, gives identical per-read
    results and stats (``check_against_host`` raises otherwise)."""
    out = cs.phase_fabric(TINY_FABRIC, TINY_TRAFFIC)
    assert out["reads"] == TINY_TRAFFIC["n_reads"]
    assert 0 < out["replica_hits"] < out["reads"]
    assert out["write_batches"] == 3          # storms at 0, 128, 256 served
    assert out["inval_msgs"] == 0


def test_host_check_catches_a_diverged_read():
    """A single altered read result must fail the oracle comparison."""
    from repro.coherence.fabric import ArrayFabric, FabricConfig

    cfg = FabricConfig(**TINY_FABRIC)
    served = cs.serve_stream(ArrayFabric(cfg, n_nodes=1,
                                         replicas_per_node=2),
                             **TINY_TRAFFIC)
    read = next(c for c in served["calls"]
                if c[0] == "read" and any(r is not None for r in c[3]))
    j = next(i for i, r in enumerate(read[3]) if r is not None)
    read[3][j] = (read[3][j][0], -7)
    with pytest.raises(AssertionError, match="read results differ"):
        cs.check_against_host(cfg, served)


def test_engine_phase_matches_recorded_counters():
    """The full phase-c sweep on the CPU reproduces ENGINE_EXPECTED — the
    values the chip's run is held to."""
    got = cs.phase_engine(expected=cs.ENGINE_EXPECTED)
    assert set(got) == set(cs.ENGINE_EXPECTED)


def test_count_all_gathers():
    hlo = ("%all-gather.5 = s32[8]{0} all-gather(%p), dimensions={0}\n"
           "%all-gather-start = (s32[2], s32[8]) all-gather-start(%q)\n"
           "%all-gather-done = s32[8]{0} all-gather-done(%all-gather-start)\n"
           "%b = s32[8]{0} bitcast(%all-gather.5)\n")
    assert cs.count_all_gathers(hlo) == 2


def _run(args, cwd, **env):
    full = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=900)


def test_sharded_phase_on_four_cpu_devices():
    """``--chips 4``'s phase on four virtual CPU devices: the sharded
    fabric equals one device, with one all-gather per exchange."""
    code = ("import chip_smoke as cs, json; print(json.dumps("
            f"cs.phase_sharded(4, {TINY_FABRIC!r}, {TINY_TRAFFIC!r})))")
    r = _run(["-c", code], ROOT, PYTHONPATH=str(ROOT / "src"),
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4 and out["exchange_all_gathers"] == 1


def test_refuses_to_run_without_a_tpu(tmp_path):
    r = _run([str(ROOT / "chip_smoke.py")], ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    # alone, without the rest of the repository
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run([str(tmp_path / "chip_smoke.py")], tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
