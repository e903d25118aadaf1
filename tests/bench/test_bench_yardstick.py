"""The benchmark's yardstick on hand-made inputs: trace reduction, span
self time, peaks and the programs' byte models."""
import pytest

from bench.lib import peaks, spans, xplane

MS = 1_000_000          # ns


def test_busy_is_the_union_of_device_ops():
    devices = {"/device:TPU:0": [("%a = s32[8]", 0, 4 * MS),
                                 ("%b = s32[8]", 2 * MS, 4 * MS),  # overlaps
                                 ("%c = s32[8]", 10 * MS, 2 * MS)],
               "/device:TPU:1": [("%a = s32[8]", 0, 1 * MS)]}
    red = xplane.reduce_trace(devices, [], window=(0, 20 * MS))
    assert red["window_s"] == pytest.approx(0.020)
    assert red["busy_s"] == pytest.approx((0.008 + 0.001) / 2)
    assert red["ops"]["%a = s32[8]"] == pytest.approx(0.005)
    assert red["n_devices"] == 2


def test_idle_gaps_split_by_the_overlapping_host_calls():
    devices = {"/device:TPU:0": [("%k", 2 * MS, 1 * MS),
                                 ("%k", 6 * MS, 1 * MS)]}
    host = [("bench.read_dispatch", 1 * MS, 3 * MS),   # 3..4 of gap 3..6
            ("bench.read_result", 5 * MS, 3 * MS),     # 5..6 and 7..8
            ("bench.fence", 9 * MS, 2 * MS)]           # 9..10 of 7..10
    red = xplane.reduce_trace(devices, host, window=(0, 10 * MS))
    assert red["idle_by_host"] == pytest.approx({
        "bench.read_dispatch": 0.001 + 0.001,          # 1..2 and 3..4
        "bench.read_result": 0.002, "bench.fence": 0.001,
        xplane.IDLE_HOST: 0.001 + 0.001 + 0.001})      # 0..1, 4..5, 8..9
    assert red["busy_s"] == pytest.approx(0.002)
    assert sum(red["idle_by_host"].values()) == pytest.approx(0.008)


def test_window_defaults_to_all_events():
    red = xplane.reduce_trace({"/device:TPU:0": [("%k", 5 * MS, MS)]},
                              [("bench.fence", 0, 8 * MS)])
    assert red["window_s"] == pytest.approx(0.008)


def test_programs_sum_their_runs_in_the_window():
    devices = {"/device:TPU:0": [("%k", 0, 10 * MS)]}
    programs = {"/device:TPU:0": [("jit_fast", 1 * MS, 2 * MS),
                                  ("jit_fast", 5 * MS, 1 * MS),
                                  ("jit_run", 4 * MS, 1 * MS),
                                  ("jit_fast", 30 * MS, 1 * MS)]}  # outside
    red = xplane.reduce_trace(devices, [], programs, window=(0, 10 * MS))
    assert red["programs"] == pytest.approx({"jit_fast": 0.003,
                                             "jit_run": 0.001})
    assert red["program_runs"] == {"jit_fast": 2, "jit_run": 1}


def _roofline_ctx(reads, fast_s):
    return {"device_kind": "TPU v5 lite",
            "work": {"fast_read": [(reads, (4,))]},
            "trace": {"programs": {"jit_fast": fast_s, "jit_run": 9.0}}}


@pytest.mark.parametrize("reads, fast_s, share", [
    (819_000, 64e-3, 0.1),      # 52.4 MB at 819 GB/s is 64 us of 64 ms
    (819_000, 0.0, None),       # no fast read traced: nothing to read
    (0, 1e-3, None),            # no reads counted
])
def test_fast_read_roofline_reader(reads, fast_s, share):
    from bench.lib import manifest
    from conftest import ROOT

    read = manifest.reader(ROOT, "fast_read_roofline.rps")
    got = read(_roofline_ctx(reads, fast_s))
    assert got == (pytest.approx(share) if share is not None else None)


def test_top_is_sorted_and_bounded():
    d = {str(i): float(i) for i in range(20)}
    assert xplane.top(d, 3) == [["19", 19.0], ["18", 18.0], ["17", 17.0]]


def test_span_self_time_excludes_children():
    ev = [  # (name, cat, tid, t0, dur, depth, args)
        ("fabric.pack", "f", 1, 0, 100, 0, None),
        ("fabric.fast_probe", "f", 1, 100, 300, 0, None),
        ("fabric.fast_probe.device", "d", 1, 150, 200, 1, None),
        ("fabric.miss_pass", "f", 1, 400, 500, 0, None),
        ("fabric.pack", "f", 1, 410, 50, 1, None),
        ("fabric.scan", "f", 1, 470, 300, 1, None),
        ("fabric.scan.device", "d", 1, 500, 250, 2, None),
        ("fabric.pack", "f", 2, 0, 70, 0, None),      # another thread
    ]
    t = spans.totals(ev)
    assert t["fabric.pack"]["count"] == 3
    assert t["fabric.pack"]["self_s"] == pytest.approx(220e-9)
    assert t["fabric.fast_probe"]["self_s"] == pytest.approx(100e-9)
    assert t["fabric.fast_probe"]["incl_s"] == pytest.approx(300e-9)
    assert t["fabric.miss_pass"]["self_s"] == pytest.approx(150e-9)
    assert t["fabric.scan"]["self_s"] == pytest.approx(50e-9)


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops"] == 197e12
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_byte_models_by_hand():
    # the fast read over 4 ways: key id and set in (2), tag+rts x 4
    # ways (8), the hit way's version and grant sequence (2), its LRU
    # touch (1), hit/version/sequence out (3): 16 words
    assert peaks.fast_read_bytes(4) == 64
    assert peaks.fast_read_bytes(16) == 4 * (2 + 32 + 2 + 1 + 3)


def test_roofline_share():
    # 819 MB at 819 GB/s takes 1 ms; a 4 ms kernel reaches 25%
    assert peaks.roofline_share(819e6, 4e-3, "TPU v5 lite") == \
        pytest.approx(25.0)
    assert peaks.roofline_share(0, 1.0, "TPU v5 lite") is None
    assert peaks.roofline_share(10, 0.0, "TPU v5 lite") is None
