"""The benchmark's drivers rehearsed on the CPU at tiny sizes, through
``bench/run.py``'s own ``run_cell``: results compared with the plain
reference, metrics named as the manifest says; and ``correct`` coming out
false when the timed path is broken underneath."""
import time

import jax
import pytest
from conftest import STORMS_CELL, STORMS_METRICS

from bench.run import run_cell
from repro.coherence.fabric import ArrayFabric

HIT = "kv-ycsbc-96.hit"


def _run(root, workload, trace=0, seconds=0.5, seed=2 ** 31 + 7):
    return run_cell(root, workload, seed=seed, seconds=seconds, trace=trace,
                    devices=jax.devices(), t0=time.perf_counter())


def test_miss_cell_end_to_end(tiny_root):
    """The open-loop storms cell: Poisson reads, storms, TSU evictions."""
    line, info = _run(tiny_root, STORMS_CELL)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"read_p99_ms", "storm_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] == 1000 and line["failed"] == 0
    assert info["storms"] > 0 and info["tsu_evictions"] > 0
    assert info["compiles_in_window"] == 0
    assert list(line)[-1] == "checks"
    assert line["device"]["count"] == 1


def test_miss_cell_traced_reads_spans(tiny_root):
    line, _ = _run(tiny_root, STORMS_CELL, trace=1)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == set(STORMS_METRICS)
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_hit_cell_is_all_hit_waves(tiny_root):
    line, info = _run(tiny_root, HIT)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"reads_per_s", "setup_s"}
    assert info["fast_read_batches"] == info["waves"] > 0
    assert info["storms"] == 0


def test_hit_cell_traced(tiny_root):
    line, info = _run(tiny_root, HIT, trace=1)
    assert line["correct"], line["checks"]
    # the CPU trace has no device plane: device metrics stay silent
    assert set(line["metrics"]) == {"host_us_per_read.rps",
                                    "fast_probe_us_per_wave.rps"}
    assert info["traced_reads"] > 0


def _break(monkeypatch, name, fn):
    monkeypatch.setattr(ArrayFabric, name, fn)


def test_storm_leaving_state_unchanged_fails(tiny_root, monkeypatch):
    """A write pass that returns the state unchanged (after set-up, so
    the stream still has values to read)."""
    orig = ArrayFabric.write_batch
    calls = []

    def stale(self, items, replica=0, wr_lease=None):
        calls.append(1)
        if len(calls) > 18:          # set-up makes about 16 storms
            return None
        return orig(self, items, replica=replica, wr_lease=wr_lease)

    _break(monkeypatch, "write_batch", stale)
    line, _ = _run(tiny_root, STORMS_CELL)
    assert not line["correct"]
    assert line["checks"]["stats_mismatches"]["value"] > 0


def test_half_of_a_wave_left_out_fails(tiny_root, monkeypatch):
    orig = ArrayFabric.read_batch_async

    def half(self, keys, replica=0):
        h = orig(self, keys, replica=replica)
        keep = len(keys) // 2

        class Half:
            def result(self):
                out = h.result()
                return out[:keep] + [None] * (len(out) - keep)
        return Half()

    _break(monkeypatch, "read_batch_async", half)
    line, _ = _run(tiny_root, HIT)
    assert not line["correct"]
    assert line["failed"] > 0


def test_altered_answer_fails(tiny_root, monkeypatch):
    orig = ArrayFabric.read_batch_async
    n = []

    def altered(self, keys, replica=0):
        h = orig(self, keys, replica=replica)

        class One:
            def result(self):
                out = list(h.result())
                n.append(1)
                if len(n) == 60 and out[0] is not None:
                    out[0] = (out[0][0], out[0][1] + 1)
                return out
        return One()

    _break(monkeypatch, "read_batch_async", altered)
    line, _ = _run(tiny_root, HIT)
    assert not line["correct"]
    assert line["checks"]["read_mismatches"]["value"] == 1


@pytest.mark.parametrize("workload", [STORMS_CELL, HIT])
def test_same_seed_same_traffic(tiny_root, workload):
    """Every seed gets the same sizes and arrivals' count, other keys."""
    from bench.lib import manifest

    cell = manifest.cell(tiny_root, workload)
    drv = manifest.driver(tiny_root, "replay")
    a, b, c = (drv.window_traces(cell["traffic"], s, 2.0)
               for s in (2 ** 33 + 1, 2 ** 33 + 1, 2 ** 33 + 2))
    assert len(a) == len(b) == len(c) >= 1
    for x, y, z in zip(a, b, c):
        assert (x.t == y.t).all() and (x.kid == y.kid).all()
        assert len(x) == len(z) and (x.kid != z.kid).any()


def test_engine_cell_end_to_end(tiny_root):
    line, info = _run(tiny_root, "engine-fig7", seconds=1.0)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"sim_events_per_s", "setup_s"}
    assert line["attempted"] == info["sweeps"] * 6     # 2 systems x 3
    assert info["compiles_in_window"] == 0 and info["first_sweep_equal"]


def _one_more_l2_hit(counters):
    counters["l2_hits"][1, 1] += 1          # one answer, where produced


def _half_the_benchmarks_unrun(counters):
    for v in counters.values():             # their state never moves
        v[:, : v.shape[1] // 2 + 1] = 0


@pytest.mark.parametrize("fault, bad", [(_one_more_l2_hit, 1),
                                        (_half_the_benchmarks_unrun, None)])
def test_engine_fault_fails(tiny_root, monkeypatch, fault, bad):
    from repro.core import engine

    orig = engine.sweep

    def broken(cfgs, ops, addrs):
        res = orig(cfgs, ops, addrs)
        fault(res["counters"])
        return res

    monkeypatch.setattr(engine, "sweep", broken)
    line, _ = _run(tiny_root, "engine-fig7", seconds=0.2)
    assert not line["correct"]
    got = line["checks"]["counter_mismatches"]["value"]
    assert got == bad if bad is not None else got > 0
