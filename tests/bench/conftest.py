"""A tiny copy of the benchmark (same manifest, same code, small sizes)
that the tests drive on the CPU through the functions ``bench/run.py``
calls."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CONFIG = dict(n_shards=4, tsu_capacity=64, replica_sets=64,
                   replica_ways=2, shared_sets=128, shared_ways=2)
TINY_TRAFFIC = {
    "ycsbc-96.hit": dict(keys=16, chunk_reads=256, chunks=2, max_batch=64,
                         warmup_reads=64, trace_seconds=2.0),
}
# An open-loop mix with publish storms, as a later serving cell would add
# it: Poisson reads over more keys than the tiers and the TSU hold, a
# 32-key storm and fence every 128 reads.
STORMS_CELL = "kv-storms.tiny"
STORMS_MIX = dict(
    driver="replay", why="test", keys=512, key_format="user{}", zipf_a=0.99,
    process="poisson", rate_rps=2000.0, max_batch=64, max_wait_s=0.005,
    min_bucket=8, storm_every=128, storm_n=32, warmup_reads=256,
    trace_seconds=0.3, publish_from=256)
STORMS_METRICS = ("wave_size.p99", "host_us_per_read.p99",
                  "miss_pass_ms_per_wave.p99", "exchange_ms_per_wave.p99",
                  "write_pass_ms_per_storm")
TINY_ENGINE = dict(n_gpus=2, cus_per_gpu=4)
TINY_SWEEP = dict(rounds=1024, benches=("aes", "bs", "fws"))


def add_storms_cell(root: Path, name=STORMS_CELL, config="lease-kv-8x16384",
                    mix="storms", chips=1, **over) -> str:
    """Add an open-loop serving cell with publish storms to the benchmark
    at ``root`` as new files and manifest entries alone."""
    (root / "bench" / "traffic" / f"{mix}.json").write_text(
        json.dumps({**STORMS_MIX, **over}))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": name, "config": config,
                             "traffic": mix, "chips": chips, "why": "test"})
    for m, unit in (("read_p99_ms", "ms"), ("storm_ms", "ms")):
        e2e = {e["name"]: e for e in man["end_to_end"]}
        if m in e2e:
            e2e[m]["workloads"].append(name)
        else:
            man["end_to_end"].insert(0, {
                "name": m, "unit": unit, "better": "lower", "bound": 0.25,
                "source": "host_clock", "workloads": [name]})
    pl = {p["name"]: p for p in man["per_layer"]}
    for m in STORMS_METRICS:
        if m in pl:
            pl[m]["workloads"].append(name)
        else:
            man["per_layer"].append({
                "name": m, "unit": "ms", "better": "lower",
                "source": "program_span", "layer": "test",
                "moves": "storm_ms" if "storm" in m else "read_p99_ms",
                "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return name


def make_tiny_root(dst: Path) -> Path:
    """The benchmark's manifest and ``bench/`` copied to ``dst``, every
    configuration and traffic file cut to a CPU-sized deployment (the hit
    mix's 16 keys fit the 128-line replica tier; the engine at 2 GPUs x 4
    CUs over 3 benchmarks of 1,024 rounds), plus the storms cell."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    for conf in (dst / "bench" / "configs").glob("lease-kv-*.json"):
        c = json.loads(conf.read_text())
        c.update(TINY_CONFIG)
        conf.write_text(json.dumps(c))
    conf = dst / "bench" / "configs" / "halcone-fig7-4x32.json"
    c = json.loads(conf.read_text())
    c["common"].update(TINY_ENGINE)
    conf.write_text(json.dumps(c))
    mix = dst / "bench" / "traffic" / "fig7.sweep.json"
    t = json.loads(mix.read_text())
    t["rounds"] = TINY_SWEEP["rounds"]
    t["benchmarks"] = {b: t["benchmarks"][b] for b in TINY_SWEEP["benches"]}
    mix.write_text(json.dumps(t))
    for mix, over in TINY_TRAFFIC.items():
        f = dst / "bench" / "traffic" / f"{mix}.json"
        f.write_text(json.dumps({**json.loads(f.read_text()), **over}))
    add_storms_cell(dst)
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))
