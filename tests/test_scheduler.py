"""Continuous-batcher tests: firing semantics (deterministic via
``service_model``), pow2 bucketing, the continuous-beats-fixed goodput
property, ``form_waves``, and open-loop replay against a real fabric."""
import numpy as np
import pytest

from repro.coherence.fabric import ArrayFabric, FabricConfig
from repro.obs import trace as obs_trace
from repro.runtime import scheduler
from repro.runtime.loadgen import RequestTrace, synthesize
from repro.runtime.scheduler import (BatchPolicy, form_waves, pad_to_bucket,
                                     replay)


def mk_trace(t, kid=None, n_keys=8):
    t = np.asarray(t, np.float64)
    if kid is None:
        kid = np.arange(len(t)) % n_keys
    return RequestTrace(t=t, kid=np.asarray(kid, np.int32), n_keys=n_keys)


class FakeHandle:
    def __init__(self, keys):
        self.keys = keys

    def result(self):
        return [f"v:{k}" for k in self.keys]


class FakeBackend:
    """Records the call stream; instant service (virtual time modeled)."""

    def __init__(self):
        self.calls = []

    def read_batch_async(self, keys, replica=1):
        self.calls.append(("read", list(keys)))
        return FakeHandle(keys)

    def write_batch(self, items, replica=0):
        self.calls.append(("write", [k for k, _ in items]))

    def fence(self):
        self.calls.append(("fence",))


SVC = lambda b: 0.010          # flat 10 ms per fabric call, any size


# ------------------------------------------------------------------ policy
def test_policy_validation_and_bucketing():
    with pytest.raises(ValueError):
        BatchPolicy(mode="sometimes")
    with pytest.raises(ValueError):
        BatchPolicy(max_batch=0)
    p = BatchPolicy(max_batch=16, min_bucket=8)
    assert pad_to_bucket(list("abc"), p) == ["a", "b", "c", "a", "b", "c",
                                             "a", "b"]          # min bucket
    assert len(pad_to_bucket(list(range(9)), p)) == 16          # next pow2
    assert pad_to_bucket(list(range(8)), p) == list(range(8))   # exact fit
    assert pad_to_bucket([], p) == []
    raw = BatchPolicy(max_batch=16, bucket=False)
    assert pad_to_bucket(list("abc"), raw) == ["a", "b", "c"]


# ---------------------------------------------------------- firing semantics
def test_continuous_fires_partial_at_deadline():
    # 3 requests at t=0 + a straggler at t=1: the first wave fires partial
    # at the 5 ms deadline (the stream hasn't ended, so it must not wait
    # for the wave to fill); the straggler drains as a final fire the
    # moment the stream ends (no point waiting — nothing else can arrive)
    tr = mk_trace([0.0, 0.0, 0.0, 1.0])
    pol = BatchPolicy(mode="continuous", max_batch=8, max_wait_s=5e-3,
                      min_bucket=4)
    res = replay(FakeBackend(), tr, pol, service_model=SVC)
    assert res.fires == {"full": 0, "deadline": 1, "final": 1}
    assert res.batch_sizes == [3, 1] and res.padded_sizes == [4, 4]
    # the deadline wave waits exactly max_wait, then one dispatch quantum
    assert np.all(res.latency_s[:3] >= 5e-3 - 1e-9)
    assert np.all(res.latency_s <= 5e-3 + 2 * SVC(0) + 1e-9)


def test_continuous_drains_immediately_when_stream_ends():
    # all arrivals at t=0 and the stream is over: the partial wave fires
    # NOW as a final drain instead of burning the deadline budget
    res = replay(FakeBackend(), mk_trace([0.0, 0.0, 0.0]),
                 BatchPolicy(mode="continuous", max_batch=8,
                             max_wait_s=5e-3, min_bucket=4),
                 service_model=SVC)
    assert res.fires == {"full": 0, "deadline": 0, "final": 1}
    assert np.all(res.latency_s <= 2 * SVC(0) + 1e-9)   # no deadline wait


def test_fixed_fires_only_full_plus_final_partial():
    # 11 arrivals, max_batch=4 -> 2 full waves + 1 final partial of 3
    tr = mk_trace(np.linspace(0.0, 0.1, 11))
    pol = BatchPolicy(mode="fixed", max_batch=4, min_bucket=4)
    res = replay(FakeBackend(), tr, pol, service_model=SVC)
    assert res.fires == {"full": 2, "deadline": 0, "final": 1}
    assert res.batch_sizes == [4, 4, 3]
    assert res.padded_sizes == [4, 4, 4]
    assert res.n_requests == 11 and not np.isnan(res.latency_s).any()
    assert np.all(res.latency_s >= 0)


def test_fixed_starves_until_wave_fills():
    # one request, then a 1 s gap before the wave-filling arrivals: under
    # fixed it waits for the fill; continuous releases it at the deadline
    t = [0.0, 1.0, 1.0, 1.0]
    pol_kw = dict(max_batch=4, max_wait_s=5e-3, min_bucket=4)
    fixed = replay(FakeBackend(), mk_trace(t),
                   BatchPolicy(mode="fixed", **pol_kw), service_model=SVC)
    cont = replay(FakeBackend(), mk_trace(t),
                  BatchPolicy(mode="continuous", **pol_kw),
                  service_model=SVC)
    assert fixed.latency_s[0] >= 1.0          # starved a full second
    assert cont.latency_s[0] < 0.05           # released by the deadline
    assert fixed.fires["full"] == 1 and cont.fires["deadline"] >= 1


def test_bucket_pads_cycle_wave_own_keys():
    tr = mk_trace([0.0, 0.0, 0.0], kid=[5, 6, 7], n_keys=8)
    pol = BatchPolicy(max_batch=8, max_wait_s=1e-3, min_bucket=8)
    be = FakeBackend()
    res = replay(be, tr, pol, service_model=SVC)
    reads = [c for c in be.calls if c[0] == "read"]
    assert len(reads) == 1
    # pads are drawn from the wave's own keys — no new keys introduced
    assert reads[0][1] == [f"prefix/{k}" for k in
                           [5, 6, 7, 5, 6, 7, 5, 6]]
    assert res.events == [("read", [5, 6, 7, 5, 6, 7, 5, 6])]


def test_republish_storm_precedes_wave_and_fences():
    tr = mk_trace(np.zeros(4), kid=[0, 1, 2, 3], n_keys=8)
    pol = BatchPolicy(max_batch=4, min_bucket=4)
    be = FakeBackend()
    res = replay(be, tr, pol, republish_every=1, republish_n=3,
                 service_model=SVC)
    kinds = [c[0] for c in be.calls]
    assert kinds == ["write", "fence", "read"]
    assert [e[0] for e in res.events] == ["write", "fence", "read"]
    assert res.events[0][1] == [0, 1, 2]      # round-robin republish slice
    assert res.walls["republish_s"] > 0


# ------------------------------------------------- continuous beats fixed
def test_continuous_goodput_dominates_fixed_on_trickle():
    """The headline property, provable under the deterministic service
    model: on a trickle (arrival gap >> service), fixed-size waves starve
    the batch while continuous releases at the deadline."""
    tr = synthesize(200, 16, process="poisson", rate=100.0, seed=3)
    kw = dict(max_batch=32, max_wait_s=20e-3, min_bucket=8)
    cont = replay(FakeBackend(), tr, BatchPolicy(mode="continuous", **kw),
                  service_model=SVC)
    fixed = replay(FakeBackend(), tr, BatchPolicy(mode="fixed", **kw),
                   service_model=SVC)
    slo = 50e-3                                # deadline + a few quanta
    ok_c, att_c = cont.goodput(slo)
    ok_f, att_f = fixed.goodput(slo)
    assert ok_c + ok_f == round(att_c * 200) + round(att_f * 200)
    assert att_c > att_f                       # strictly better here
    assert att_c > 0.9
    # same request count either way; nothing lost
    assert cont.n_requests == fixed.n_requests == 200


# ---------------------------------------------------------------- form_waves
def test_form_waves_matches_replay_semantics():
    items = list("abcdefghijk")
    t = np.linspace(0.0, 0.1, len(items))
    fixed = form_waves(t, items, BatchPolicy(mode="fixed", max_batch=4))
    assert fixed == [list("abcd"), list("efgh"), list("ijk")]
    # continuous with a huge deadline behaves like fixed
    cont = form_waves(t, items, BatchPolicy(max_batch=4, max_wait_s=10.0))
    assert cont == fixed
    # continuous with a tiny deadline fires singletons on a slow trickle
    slow = form_waves(np.arange(5) * 1.0, list(range(5)),
                      BatchPolicy(max_batch=4, max_wait_s=1e-3))
    assert slow == [[0], [1], [2], [3], [4]]
    assert form_waves([], [], BatchPolicy()) == []
    with pytest.raises(ValueError):
        form_waves([0.0], [], BatchPolicy())
    with pytest.raises(ValueError):
        form_waves([1.0, 0.5], ["a", "b"], BatchPolicy())


def test_form_waves_preserves_order_and_items():
    tr = synthesize(300, 8, process="burst", rate=50.0, seed=1)
    waves = form_waves(tr.t, list(range(300)),
                       BatchPolicy(max_batch=16, max_wait_s=10e-3))
    flat = [x for w in waves for x in w]
    assert flat == list(range(300))            # order kept, nothing dropped
    assert all(0 < len(w) <= 16 for w in waves)


# ----------------------------------------------------------- real fabric
SMALL = dict(n_shards=2, rd_lease=16, wr_lease=4, replica_sets=16,
             replica_ways=4, shared_sets=32, shared_ways=4)


def test_replay_against_array_fabric():
    """End-to-end open-loop replay on a real single-device fabric: values
    resolve correctly, stats move, and the ordering contract holds."""
    fab = ArrayFabric(FabricConfig(**SMALL), n_nodes=1, replicas_per_node=2)
    n_keys = 8
    fab.write_batch([(f"prefix/{k}", f"v@init") for k in range(n_keys)],
                    replica=0)
    fab.fence()
    tr = synthesize(60, n_keys, process="poisson", rate=500.0, seed=6)
    pol = BatchPolicy(max_batch=8, max_wait_s=2e-3, min_bucket=8)
    res = replay(fab, tr, pol, republish_every=4, republish_n=4)
    assert res.n_requests == 60
    assert np.all(res.latency_s >= 0) and res.t_end > 0
    assert sum(res.batch_sizes) == 60
    assert all(p in (8, 16) for p in res.padded_sizes)
    assert res.fires["full"] + res.fires["deadline"] + res.fires["final"] \
        == len(res.batch_sizes)
    st = fab.stats()
    assert st["reads"] >= sum(res.padded_sizes)
    assert st["fast_read_batches"] >= 0 and st["write_batches"] > 0
    # the event stream replays the same reads the fabric saw
    n_read_rows = sum(len(e[1]) for e in res.events if e[0] == "read")
    assert n_read_rows == sum(res.padded_sizes)


# -------------------------------------------------------------------- spans
def _traced(fn):
    """Run ``fn`` under an enabled scoped tracer: (result, span events)."""
    tr = obs_trace.Tracer(enabled=True)
    old = obs_trace.set_tracer(tr)
    try:
        return fn(), tr.events
    finally:
        obs_trace.set_tracer(old)


def _parents(events):
    """(name, direct parent's name or None) for every span event."""
    by_tid = {}
    for ev in events:
        by_tid.setdefault(ev[2], []).append(ev)
    out = []
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e[3], e[5]))
        stack = []
        for name, _cat, _tid, _t0, _dur, depth, _args in evs:
            del stack[depth:]
            out.append((name, stack[-1] if stack else None))
            stack.append(name)
    return out


def test_one_dispatch_span_per_wave_with_its_wave_number():
    tr = synthesize(200, 16, process="poisson", rate=100.0, seed=3)
    pol = BatchPolicy(max_batch=32, max_wait_s=20e-3, min_bucket=8)
    res, events = _traced(lambda: replay(
        FakeBackend(), tr, pol, service_model=SVC, republish_every=50,
        republish_n=4))
    waves = list(range(len(res.batch_sizes)))
    wave_of = lambda name: [e[6]["wave"] for e in events if e[0] == name]
    assert len(waves) > 4
    assert wave_of("sched.dispatch") == waves
    assert wave_of("sched.keys") == waves
    assert sorted(wave_of("sched.resolve")) == waves
    assert len(wave_of("sched.storm")) == \
        sum(e[0] == "fence" for e in res.events) == 4
    assert [e[6] for e in events if e[0] == "sched.replay"] == \
        [{"n_requests": 200}]
    assert all(e[1] == "sched" for e in events)


def test_span_count_grows_with_waves_not_requests():
    def spans(n_requests, max_batch):
        tr = mk_trace(np.zeros(n_requests), n_keys=8)
        pol = BatchPolicy(max_batch=max_batch, min_bucket=8)
        res, events = _traced(lambda: replay(FakeBackend(), tr, pol,
                                             service_model=SVC))
        return len(res.batch_sizes), len(events)

    four_small, four_large, eight = spans(32, 8), spans(256, 64), \
        spans(64, 8)
    assert four_small[0] == four_large[0] == 4 and eight[0] == 8
    assert four_small[1] == four_large[1] < eight[1]


def _served_fabric():
    fab = ArrayFabric(FabricConfig(**SMALL), n_nodes=1, replicas_per_node=2)
    fab.write_batch([(f"prefix/{k}", "v@init") for k in range(8)],
                    replica=0)
    fab.fence()
    return fab


def _fabric_replay(fab):
    tr = synthesize(60, 8, process="poisson", rate=500.0, seed=6)
    pol = BatchPolicy(max_batch=8, max_wait_s=2e-3, min_bucket=8)
    return replay(fab, tr, pol, republish_every=16, republish_n=4,
                  service_model=lambda b: 1e-4 * b)


def test_fabric_spans_nest_under_scheduler_spans():
    fab = _served_fabric()
    _, events = _traced(lambda: _fabric_replay(fab))
    pairs = _parents(events)
    fabric_roots = {p for name, p in pairs if name.startswith("fabric.")
                    and not p.startswith("fabric.")}
    # a wave with misses decodes its miss pass when the handle resolves
    assert {"sched.dispatch", "sched.storm"} <= fabric_roots <= \
        {"sched.dispatch", "sched.storm", "sched.resolve"}
    assert {p for name, p in pairs if name.startswith("sched.")} == \
        {None, "sched.replay", "sched.dispatch"}
    assert [name for name, p in pairs if p is None] == ["sched.replay"]


def test_replay_result_identical_traced_and_untraced():
    plain = _fabric_replay(_served_fabric())
    fab = _served_fabric()
    traced, events = _traced(lambda: _fabric_replay(fab))
    assert events
    assert np.array_equal(plain.latency_s, traced.latency_s)
    for field in ("t_end", "batch_sizes", "padded_sizes", "fires", "walls",
                  "events"):
        assert getattr(plain, field) == getattr(traced, field), field
