"""The plain reference of the lease fabric agrees with the program's own
host oracle on random streams, and its control does not: the control,
put in the program's place in a tiny cell, makes ``correct`` false."""
import random

import pytest
from conftest import STORMS_CELL

from bench.refs.lease_kv import LeaseKV, NoWriteThroughKV
from bench.tools.control import RefBackend, control_checks
from repro.coherence.fabric import FabricConfig, HostFabric

GEOMETRIES = [
    dict(n_shards=4, tsu_capacity=16, replica_sets=8, replica_ways=2,
         shared_sets=16, shared_ways=2, max_in_flight=3),
    dict(n_shards=8, tsu_capacity=32, replica_sets=16, replica_ways=4,
         shared_sets=32, shared_ways=8, max_in_flight=8),
    # leases long enough that the 16-bit clocks wrap and re-initialise
    dict(n_shards=2, tsu_capacity=16, replica_sets=8, replica_ways=2,
         shared_sets=16, shared_ways=2, max_in_flight=2, rd_lease=3000,
         wr_lease=2000),
]


def _stream(fab, ref, seed, steps=1500, keys=100):
    rng = random.Random(seed)
    for step in range(steps):
        x = rng.random()
        rep = rng.randrange(2)
        if x < 0.15:
            items = [(f"k{rng.randrange(keys)}", f"v{step}.{j}")
                     for j in range(rng.randrange(1, 20))]
            fab.write_batch(items, replica=rep)
            ref.write_batch(items, rep)
        elif x < 0.2:
            yield fab.fence(), ref.fence()
        else:
            ks = [f"k{rng.randrange(keys)}"
                  for _ in range(rng.randrange(1, 30))]
            yield fab.read_batch(ks, replica=rep), ref.read_batch(ks, rep)


@pytest.mark.parametrize("geo", GEOMETRIES)
@pytest.mark.parametrize("seed", [0, 1])
def test_reference_equals_host_oracle(geo, seed):
    cfg = FabricConfig(**geo)
    fab = HostFabric(cfg, n_nodes=1, replicas_per_node=2)
    ref = LeaseKV(n_nodes=1, replicas_per_node=2, rd_lease=cfg.rd_lease,
                  wr_lease=cfg.wr_lease, **{k: v for k, v in geo.items()
                                            if "lease" not in k})
    for got, want in _stream(fab, ref, seed):
        assert got == want
    assert fab.stats() == ref.stats()
    if geo.get("rd_lease") == 3000:
        assert ref.stats()["overflow_reinits"] > 0
    if geo["tsu_capacity"] * geo["n_shards"] < 100:
        assert ref.stats()["tsu_evictions"] > 0


def test_control_differs_from_reference():
    geo = dict(GEOMETRIES[0], rd_lease=8, wr_lease=4)
    ctl = RefBackend(NoWriteThroughKV(replicas_per_node=2, **geo))
    ref = LeaseKV(replicas_per_node=2, **geo)
    assert sum(a != b for a, b in _stream(ctl, ref, 3, steps=300)) > 0


@pytest.mark.parametrize("workload, number", [
    (STORMS_CELL, "read_mismatches"),
    ("kv-ycsbc-96.hit", "read_mismatches"),
    ("engine-fig7", "counter_mismatches")])
def test_control_in_the_programs_place_is_not_correct(tiny_root, workload,
                                                      number):
    checks, compared = control_checks(tiny_root, workload, seed=11,
                                      seconds=0.5)
    assert compared > 0
    assert checks[number] > 0


def test_engine_reference_equals_the_engine():
    """Every counter of the five systems at 4 GPUs x 8 CUs over three
    benchmarks: CUs of a GPU share L2 sets and TSU entries, so same-round
    installs into one slot happen."""
    import dataclasses

    from bench.lib import engine_traces
    from bench.refs import halcone_engine as he
    from repro.core import engine, traces
    from repro.core.sysconfig import ALL_CONFIGS

    cfgs = [mk(pcie_lat=1000.0, n_gpus=4, cus_per_gpu=8)
            for mk in ALL_CONFIGS]
    base = dataclasses.asdict(cfgs[0])
    ops, addrs = engine_traces.pack_batch([
        engine_traces.standard_trace(
            base, dataclasses.asdict(traces.STANDARD[b]), 128, [5, i])
        for i, b in enumerate(("bs", "fws", "mm"))])
    res = engine.sweep(cfgs, ops, addrs)
    for i, c in enumerate(cfgs):
        want = he.simulate(dataclasses.asdict(c), ops, addrs)
        for k in he.COUNTERS:
            assert (res["counters"][k][i].astype(int) == want[k]).all(), \
                (c.name, k)
    assert (res["counters"]["coh_miss_l1"][4] > 0).any()
    assert (res["counters"]["inval_msgs"][1] > 0).any()
