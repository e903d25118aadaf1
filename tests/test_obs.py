"""Observability layer acceptance (DESIGN.md §10).

Four contracts:

  * histogram percentiles are numpy-exact while samples are retained and
    a sane bucket interpolation past the cap;
  * a REAL traced fabric batch exports schema-valid Chrome-trace JSON
    whose spans form a well-nested forest (strict stack discipline);
  * enabled spans are mirrored into a running ``jax.profiler`` trace by
    name, nested as they ran; disabled ones write nothing there;
  * the <1% gate: with tracing disabled (the default), the span
    instrumentation left on one replayed serving wave (scheduler and
    fabric) costs under 1% of a serving batch — the paper's own overhead
    bar (§6.2) applied to our own telemetry.
"""
import json

import numpy as np
import pytest

from repro.coherence.fabric import ArrayFabric, FabricConfig
from repro.obs import LatencyHistogram
from repro.obs import trace as obs_trace
from repro.obs.xprof import cost_probe, jaxpr_collectives


# ------------------------------------------------------------- histograms
def test_percentiles_match_numpy_exactly():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-7.0, sigma=2.0, size=4096)  # ~µs..s
    h = LatencyHistogram()
    h.record_many(samples)
    assert h.exact
    for p in (0, 10, 50, 90, 95, 99, 99.9, 100):
        np.testing.assert_allclose(h.percentile(p),
                                   np.percentile(samples, p),
                                   rtol=0, atol=0, err_msg=f"p{p}")
    s = h.summary()
    assert s["count"] == len(samples) and s["exact"]
    np.testing.assert_allclose(s["p99_us"],
                               round(np.percentile(samples, 99) * 1e6, 2))


def test_percentiles_degrade_to_bucket_interpolation_past_cap():
    rng = np.random.default_rng(1)
    samples = rng.lognormal(mean=-9.0, sigma=1.0, size=512)
    h = LatencyHistogram(sample_cap=64)
    h.record_many(samples)
    assert not h.exact and not h.summary()["exact"]
    exact = np.percentile(samples, 95)
    est = h.percentile(95)
    # log-bucket estimate lands within one growth factor of the truth
    assert exact / 2.0 <= est <= exact * 2.0
    rows = h.buckets()
    assert rows[-1] == (float("inf"), len(samples))
    cum = [c for _, c in rows]
    assert cum == sorted(cum)                      # cumulative, monotone


def test_histogram_validation_and_merge():
    h = LatencyHistogram()
    with pytest.raises(ValueError):
        h.record(-1e-3)
    a = LatencyHistogram().record_many([1e-3, 2e-3])
    b = LatencyHistogram().record_many([4e-3])
    a.merge(b)
    assert a.count == 3 and a.max_s == 4e-3
    with pytest.raises(ValueError):
        a.merge(LatencyHistogram(base=1e-3))


# ------------------------------------------------------- trace well-formed
def _traced_fabric_batch():
    """Run one miss-heavy + one all-hit batch under a scoped tracer."""
    fab = ArrayFabric(FabricConfig(n_shards=4, rd_lease=8, wr_lease=4))
    hot = [f"k/{i}" for i in range(32)]
    fab.write_batch([(k, f"{k}@0") for k in hot], replica=0)
    fab.fence()
    tr = obs_trace.Tracer(enabled=True)
    old = obs_trace.set_tracer(tr)
    try:
        fab.read_batch(hot, replica=1)             # misses -> miss pass
        fab.read_batch(hot, replica=1)             # all-hit fast path
    finally:
        obs_trace.set_tracer(old)
    return tr


def test_trace_spans_form_a_wellnested_forest():
    tr = _traced_fabric_batch()
    names = {e[0] for e in tr.events}
    assert {"fabric.pack", "fabric.fast_probe", "fabric.decode",
            "fabric.miss_pass", "fabric.scan",
            "fabric.scan.device"} <= names
    # per-thread, spans nest strictly: sweep by start time with a stack
    # of (start, end) — every span lies inside its enclosing one
    by_tid = {}
    for name, _cat, tid, t0, dur, _depth, _args in tr.events:
        by_tid.setdefault(tid, []).append((t0, t0 + dur, name))
    for spans in by_tid.values():
        spans.sort(key=lambda s: (s[0], -s[1]))
        stack = []
        for t0, t1, name in spans:
            while stack and t0 >= stack[-1][1]:
                stack.pop()
            assert not stack or t1 <= stack[-1][1], \
                f"{name} crosses its parent"
            stack.append((t0, t1))


def test_trace_exports_valid_chrome_json(tmp_path):
    tr = _traced_fabric_batch()
    path = tr.export(tmp_path / "trace.json")
    blob = json.loads(path.read_text())
    assert blob["displayTimeUnit"] == "ms"
    events = blob["traceEvents"]
    assert events, "no events exported"
    for ev in events:
        assert ev["ph"] == "X"                     # complete events
        assert isinstance(ev["name"], str) and isinstance(ev["cat"], str)
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0
        assert isinstance(ev["dur"], float) and ev["dur"] >= 0
        assert set(ev) <= {"name", "cat", "ph", "ts", "dur", "pid",
                           "tid", "args"}
    # the device-execute child sits inside its dispatch span
    scans = [e for e in events if e["name"] == "fabric.scan"]
    fences = [e for e in events if e["name"] == "fabric.scan.device"]
    assert scans and fences
    s, f = scans[0], fences[0]
    assert s["ts"] <= f["ts"] and \
        f["ts"] + f["dur"] <= s["ts"] + s["dur"] + 1e-3


def test_disabled_tracing_records_nothing_and_passes_values():
    tr = obs_trace.Tracer(enabled=False)
    old = obs_trace.set_tracer(tr)
    try:
        with obs_trace.span("x"):
            pass
        sentinel = object()
        assert obs_trace.fence(sentinel) is sentinel
    finally:
        obs_trace.set_tracer(old)
    assert tr.events == []


# ------------------------------------------------ mirrored into the profiler
def _profiled_host_events(tmp_path, enabled):
    """Run one span with a fenced child under a CPU ``jax.profiler``
    trace; returns the host-plane events named ``obs_test.*`` as (line,
    name, start_ns, end_ns, stats)."""
    import glob

    import jax
    import jax.numpy as jnp

    tr = obs_trace.Tracer(enabled=enabled)
    old = obs_trace.set_tracer(tr)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("obs_test.outer", wave=7):
            obs_trace.fence(jnp.arange(8) + 1, "obs_test.outer.device")
    finally:
        jax.profiler.stop_trace()
        obs_trace.set_tracer(old)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("obs_test.")]


def test_enabled_spans_land_nested_on_the_profiler_host_plane(tmp_path):
    evs = {name: (line, s, e, stats) for line, name, s, e, stats
           in _profiled_host_events(tmp_path, enabled=True)}
    assert set(evs) == {"obs_test.outer", "obs_test.outer.device"}
    line, s, e, stats = evs["obs_test.outer"]
    cline, cs, ce, _ = evs["obs_test.outer.device"]
    assert cline == line and s <= cs and ce <= e and cs < ce
    assert stats == {"wave": 7}               # the span's arguments


def test_disabled_tracer_writes_no_profiler_annotation(tmp_path):
    assert _profiled_host_events(tmp_path, enabled=False) == []


# --------------------------------------------------------- <1% overhead gate
def test_disabled_overhead_under_one_percent_of_serving_batch():
    """The acceptance gate: the spans of one replayed serving wave
    (``scheduler.replay``'s and the fabric's) x the measured cost of one
    DISABLED span < 1% of the batched serving path's p50.
    (Methodology in DESIGN.md §10d — the uninstrumented build no longer
    exists to A/B against, and this decomposition is noise-immune.)"""
    from repro.runtime import scheduler
    from repro.runtime.loadgen import RequestTrace

    cfg = FabricConfig(n_shards=4, rd_lease=64, wr_lease=4,
                       replica_sets=512, replica_ways=8,
                       shared_sets=1024, shared_ways=8)
    fab = ArrayFabric(cfg, n_nodes=2, replicas_per_node=2)
    hot = [f"prefix/{i}" for i in range(2048)]
    fab.write_batch([(k, f"{k}@0") for k in hot], replica=0)
    fab.fence()
    fab.read_batch(hot, replica=1)                 # fill + compile
    h = LatencyHistogram()
    import time
    for _ in range(12):
        t0 = time.perf_counter()
        fab.read_batch(hot, replica=1)             # all-hit steady state
        h.record(time.perf_counter() - t0)
    p50_us = h.summary()["p50_us"]
    # count the spans of this batch replayed as one scheduler wave: the
    # root, the end-of-stream drain and the fabric's spans included
    wave = RequestTrace(t=np.zeros(len(hot)),
                        kid=np.arange(len(hot), dtype=np.int32),
                        n_keys=len(hot))
    policy = scheduler.BatchPolicy(max_batch=len(hot))
    tr = obs_trace.Tracer(enabled=True)
    old = obs_trace.set_tracer(tr)
    try:
        res = scheduler.replay(fab, wave, policy)
    finally:
        obs_trace.set_tracer(old)
    assert res.batch_sizes == [len(hot)]
    names = [e[0] for e in tr.events]
    assert {"sched.dispatch", "fabric.pack", "fabric.fast_probe",
            "fabric.donate", "fabric.decode"} <= set(names)
    spans = len(names)
    span_ns = obs_trace.disabled_span_cost_ns()
    overhead_pct = 100.0 * (spans * span_ns / 1e3) / p50_us
    assert overhead_pct < 1.0, (
        f"{spans} spans x {span_ns:.0f}ns = "
        f"{spans * span_ns / 1e3:.1f}us on a {p50_us:.0f}us batch "
        f"({overhead_pct:.2f}% > 1%)")


# ------------------------------------------------------------------ xprof
def test_jaxpr_collectives_counts_loop_bodies():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec

    def body(c, x):
        return c + jax.lax.psum(x, "i"), x

    def fn(xs):
        c, _ = jax.lax.scan(body, jnp.float32(0), xs)
        return c + jax.lax.psum(c, "i")

    mesh = Mesh(np.array(jax.devices()[:1]), ("i",))
    jaxpr = jax.make_jaxpr(
        jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec("i"),
                      out_specs=PartitionSpec(), check_vma=False)
    )(jnp.ones((8,), jnp.float32))
    c = jaxpr_collectives(jaxpr)
    assert c["total"] == 2 and c["in_loop"] == 1 and c["loops"] >= 1
    assert sum(c["by_primitive"].values()) == c["total"]

    # pipeline.collective_counts now delegates here: same numbers
    from repro.coherence.fabric.pipeline import collective_counts
    legacy = collective_counts(jaxpr)
    assert legacy == {"total": c["total"], "in_loop": c["in_loop"]}


def test_cost_probe_reports_structure_and_cost():
    import jax.numpy as jnp

    def fn(a, b):
        return a @ b

    a = jnp.ones((64, 64), jnp.float32)
    probe = cost_probe(fn, a, a)
    assert probe["collectives"]["total"] == 0
    # XLA's cost analysis is backend-dependent; when present it must see
    # the matmul's FLOPs
    if probe["flops"] is not None:
        assert probe["flops"] >= 2 * 64 ** 3 * 0.9
    if probe["bytes_accessed"] is not None:
        assert probe["bytes_accessed"] > 0
