"""The scheduler's spans as the benchmark reads them, on the CPU: the
idle split by host spans on hand-made traces (``bench/lib/host_spans``),
the ``sched_us_per_read`` reader, and ``bench/tools/idle_split.py``
over the tiny cells."""
import time

import jax
import pytest

from bench.lib import compile_meter, host_spans, manifest, spans, xplane
from bench.tools import idle_split

MS = 1_000_000          # ns
HIT = "kv-ycsbc-96.hit"


# one device busy 2..3 and 6..7 ms in a window the harness's annotations
# bound at 0..10 ms; the program's spans nest inside the annotations
SPLIT_DEVICES = {"/device:TPU:0": [("%k", 2 * MS, 1 * MS),
                                   ("%k", 6 * MS, 1 * MS)]}
SPLIT_HOST = [
    ("bench.read_dispatch", 0, 4 * MS),
    ("sched.dispatch", 0.5 * MS, 3 * MS),         # 0.5..3.5
    ("fabric.pack", 1 * MS, 0.5 * MS),            # 1..1.5, innermost
    ("fabric.fast_probe", 1.5 * MS, 2 * MS),      # 1.5..3.5
    ("sched.resolve", 5 * MS, 1.5 * MS),          # 5..6.5
    ("bench.fence", 8 * MS, 2 * MS),              # 8..10
    ("sched.form", 9 * MS, 3 * MS),               # past the window's end
]


def test_idle_goes_to_the_innermost_open_span():
    got = host_spans.split(SPLIT_DEVICES, SPLIT_HOST)
    assert got == pytest.approx({
        "bench.read_dispatch": 0.0005 + 0.0005,   # 0..0.5, 3.5..4
        "sched.dispatch": 0.0005,                 # 0.5..1
        "fabric.pack": 0.0005,                    # 1..1.5
        "fabric.fast_probe": 0.0005 + 0.0005,     # 1.5..2, 3..3.5
        "sched.resolve": 0.001,                   # 5..6
        "bench.fence": 0.001,                     # 8..9
        "sched.form": 0.001,                      # 9..10, clipped
        host_spans.UNSPANNED: 0.001 + 0.001,      # 4..5, 7..8
    })


def test_idle_split_sums_to_the_reduced_idle_time():
    bench = [ev for ev in SPLIT_HOST if ev[0].startswith("bench.")]
    red = xplane.reduce_trace(SPLIT_DEVICES, bench)
    got = host_spans.split(SPLIT_DEVICES, SPLIT_HOST)
    assert red["window_s"] == pytest.approx(0.010)
    assert sum(got.values()) == pytest.approx(red["window_s"] -
                                              red["busy_s"])
    # with no program span the remainder is reduce_trace's outside-calls
    bare = host_spans.split(SPLIT_DEVICES, bench)
    assert bare[host_spans.UNSPANNED] == \
        pytest.approx(red["idle_by_host"][xplane.IDLE_HOST])


def test_idle_split_without_a_device_is_empty():
    assert host_spans.split({}, SPLIT_HOST) == {}
    only = host_spans.split({"/device:TPU:0": [("%k", 0, 10 * MS)]},
                            [("bench.fence", 0, 10 * MS)])
    assert only == {host_spans.UNSPANNED: 0.0}


def _reader(name):
    from conftest import ROOT

    return manifest.reader(ROOT, name)


ON_CHIP = {"n_devices": 1}              # a traced run's reduced trace


def _totals(**self_s):
    return {k.replace("_", "."): {"count": 1, "incl_s": v, "self_s": v}
            for k, v in self_s.items()}


@pytest.mark.parametrize("sp, reads, want", [
    (_totals(sched_form=0.2, sched_keys=0.1, fabric_pack=5.0), 100_000,
     3.0),
    (_totals(fabric_pack=5.0), 100_000, None),   # no sched span
    (_totals(sched_dispatch=5.0), 100_000, None),
    (_totals(sched_form=0.2), 0, None),
    (_totals(sched_form=0.2), None, None),       # no reads counted
    ({}, 100_000, None),                         # no span recorded
    (None, 100_000, None),                       # untraced
])
def test_sched_us_per_read_reader(sp, reads, want):
    counters = {} if reads is None else {"reads": reads}
    got = _reader("sched_us_per_read.rps")(
        {"spans": sp, "counters": counters, "trace": ON_CHIP})
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("trace", [None, {"n_devices": 0}])
def test_sched_us_per_read_needs_a_device_trace(trace):
    got = _reader("sched_us_per_read.rps")(
        {"spans": _totals(sched_form=0.2), "counters": {"reads": 10},
         "trace": trace})
    assert got is None


def test_sched_us_per_read_leaves_out_the_backend_calls():
    """The self time of ``sched.dispatch`` and ``sched.storm`` is the
    backend call's entry (in a traced run, the harness's profiler stop
    too); the fabric's children and ``sched.keys`` are their own."""
    # (name, cat, tid, t0_ns, dur_ns, depth, args): one wave whose
    # dispatch holds a 5 s stop and a storm 990 ms of backend entry
    evs = [("sched.replay", "sched", 1, 0, 6000 * MS, 0, None),
           ("sched.admit", "sched", 1, 0, 1 * MS, 1, None),
           ("sched.form", "sched", 1, 1 * MS, 1 * MS, 1, None),
           ("sched.dispatch", "sched", 1, 2 * MS, 5005 * MS, 1, None),
           ("sched.keys", "sched", 1, 2 * MS, 1 * MS, 2, None),
           ("fabric.pack", "fabric", 1, 5003 * MS, 4 * MS, 2, None),
           ("sched.storm", "sched", 1, 5007 * MS, 990 * MS, 1, None),
           ("sched.resolve", "sched", 1, 5997 * MS, 3 * MS, 1, None)]
    got = _reader("sched_us_per_read.rps")(
        {"spans": spans.totals(evs), "counters": {"reads": 1000},
         "trace": ON_CHIP})
    # replay 0, admit 1, form 1, keys 1, resolve 3 ms
    assert got == pytest.approx(1e6 * 0.006 / 1000)


def test_sched_us_per_read_reads_a_traced_replay(tiny_root):
    """The driver's own context after a traced tiny hit run: the
    scheduler's spans reach it, and the reader reads them where the
    trace holds a device (the CPU trace holds none)."""
    cell = manifest.cell(tiny_root, HIT)
    drv = manifest.driver(tiny_root, cell["traffic"]["driver"])
    out = drv.run(cell, seed=2 ** 31 + 13, seconds=0.2, trace=1,
                  t0=time.perf_counter(), devices=jax.devices(),
                  meter=compile_meter.CompileMeter())
    ctx = out["ctx"]
    assert {"sched.replay", "sched.admit", "sched.form", "sched.dispatch",
            "sched.keys", "sched.resolve"} <= set(ctx["spans"])
    read = manifest.reader(tiny_root, "sched_us_per_read.rps")
    assert read(ctx) is None
    assert read({**ctx, "trace": ON_CHIP}) > 0


@pytest.mark.parametrize("workload", [HIT, "engine-fig7"])
def test_idle_split_tool_splits_the_drivers_trace(tiny_root, workload):
    load = xplane.load
    line, info, split = idle_split.traced_split(
        tiny_root, workload, seed=2 ** 31 + 11, seconds=0.2,
        devices=jax.devices())
    assert line["correct"], line["checks"]
    assert split == {}              # the CPU trace has no device plane
    assert xplane.load is load


def test_idle_split_summary():
    line = {"correct": True, "metrics": {"m": {"value": 2.0, "unit": "us"}},
            "device": {"window_s": 2.0, "busy_s": 0.5},
            "breakdown": {"idle_gaps": [["host:outside_calls", 1.5]]}}
    split = {"sched.form": 1.0, "fabric.pack": 0.45, "unspanned": 0.05}
    got = idle_split.summary(line, split, top=2)
    assert got["metrics"] == {"m": 2.0}
    assert got["idle_by_span"] == [["sched.form", 1.0],
                                   ["fabric.pack", 0.45]]
    assert got["unspanned_pct"] == pytest.approx(2.5)
    assert got["split_sum_s"] == pytest.approx(got["idle_s"])
