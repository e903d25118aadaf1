"""Continuous-batcher tests: firing semantics (deterministic via
``service_model``), pow2 bucketing, the continuous-beats-fixed goodput
property, ``form_waves``, open-loop replay against a real fabric, and
the index-range ``replay`` against the per-request oracle it replaced."""
import collections
import time

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


# ---------------------------------------------- per-request oracle replay
def oracle_replay(backend, trace, policy, *, replica=1, writer=0,
                  key_of=None, republish_every=0, republish_n=16,
                  service_model=None):
    """The per-request ``replay`` that the index-range one replaced, kept
    as the oracle: a deque of request indices, members popped one by one,
    a key string formatted per read, a completion stamp per member.
    Spans left out; they move no result."""
    key_of = key_of or (lambda k: f"prefix/{k}")
    t_arr, kids, n = trace.t, trace.kid, len(trace)
    q = collections.deque()
    i = 0
    now = 0.0
    done = np.full(n, np.nan)
    pending = None
    events, batch_sizes, padded_sizes = [], [], []
    fires = {"full": 0, "deadline": 0, "final": 0}
    walls = {"dispatch_s": 0.0, "resolve_s": 0.0, "republish_s": 0.0}
    n_waves = served = next_storm_at = n_storms = 0

    def timed(fn, modeled):
        t0 = time.perf_counter()
        fn()
        w = time.perf_counter() - t0
        return w if service_model is None else modeled

    def admit():
        nonlocal i
        while i < n and t_arr[i] <= now:
            q.append(i)
            i += 1

    def resolve_pending():
        nonlocal pending, now
        members, handle = pending
        w = timed(handle.result, 0.0)
        now += w
        walls["resolve_s"] += w
        for r in members:
            done[r] = now
        pending = None

    def try_fire():
        if not q:
            return None
        if len(q) >= policy.max_batch:
            kind = "full"
        elif (policy.mode == "continuous"
              and now - t_arr[q[0]] >= policy.max_wait_s - 1e-12):
            kind = "deadline"
        elif i >= n and pending is None:
            kind = "final"
        else:
            return None
        take = min(len(q), policy.max_batch)
        return [q.popleft() for _ in range(take)], kind

    def next_fire_time():
        cands = []
        if policy.mode == "continuous":
            if q:
                cands.append(t_arr[q[0]] + policy.max_wait_s)
            elif i < n:
                cands.append(t_arr[i] + policy.max_wait_s)
        need = policy.max_batch - len(q)
        if i + need - 1 < n:
            cands.append(t_arr[i + need - 1])
        elif i < n:
            cands.append(t_arr[n - 1])
        return min(cands) if cands else None

    def dispatch(padded, holder):
        keys = [key_of(k) for k in padded]
        holder["h"] = backend.read_batch_async(keys, replica=replica)

    while True:
        admit()
        fired = try_fire()
        if fired is not None:
            members, kind = fired
            fires[kind] += 1
            ks = [int(kids[r]) for r in members]
            padded = pad_to_bucket(ks, policy)
        if fired is None:
            if pending is not None:
                resolve_pending()
                continue
            nft = next_fire_time()
            if nft is None:
                break
            now = max(now, nft)
            continue
        if republish_every and served >= next_storm_at:
            if pending is not None:
                resolve_pending()
            sl = [(n_storms * republish_n + j) % trace.n_keys
                  for j in range(republish_n)]
            w = timed(
                lambda: (backend.write_batch(
                    [(key_of(k), f"v@{n_waves}") for k in sl],
                    replica=writer), backend.fence()),
                service_model(len(sl)) if service_model else 0.0)
            now += w
            walls["republish_s"] += w
            events.append(("write", sl))
            events.append(("fence",))
            n_storms += 1
            next_storm_at += republish_every
        if pending is not None:
            resolve_pending()
        holder = {}
        w = timed(lambda: dispatch(padded, holder),
                  service_model(len(padded)) if service_model else 0.0)
        now += w
        walls["dispatch_s"] += w
        events.append(("read", list(padded)))
        batch_sizes.append(len(ks))
        padded_sizes.append(len(padded))
        pending = (members, holder["h"])
        n_waves += 1
        served += len(members)
    if pending is not None:
        resolve_pending()
    return scheduler.ReplayResult(
        latency_s=done - t_arr, t_end=now, batch_sizes=batch_sizes,
        padded_sizes=padded_sizes, fires=fires, walls=walls, events=events)


class CallLog:
    """Records every backend call with all its arguments, the way the
    serving benchmark's proxy does; read results are the key strings."""

    def __init__(self):
        self.calls = []

    def read_batch_async(self, keys, replica=1):
        self.calls.append(("read", keys, replica))
        return FakeHandle(keys)

    def write_batch(self, items, replica=0):
        self.calls.append(("write", list(items), replica))

    def fence(self):
        self.calls.append(("fence",))


def _arrivals(kind, n, seed):
    if kind == "backlog":
        return mk_trace(np.zeros(n),
                        np.random.default_rng(seed).integers(0, 40, n),
                        n_keys=48)
    return synthesize(n, 48, process=kind, rate=4000.0, seed=seed)


SVC_BY_SIZE = lambda b: 2e-4 + 1e-5 * b     # deterministic, size-dependent


@pytest.mark.parametrize("storm_every", [0, 37])
@pytest.mark.parametrize("max_batch,min_bucket,bucket", [
    (16, 8, True),          # power-of-two waves
    (12, 8, True),          # full waves pad 12 -> 16
    (6, 32, True),          # min_bucket above every wave
    (10, 8, False),         # no padding at all
])
@pytest.mark.parametrize("arrivals", ["backlog", "poisson", "burst"])
@pytest.mark.parametrize("mode", ["continuous", "fixed"])
def test_replay_equals_per_request_oracle(mode, arrivals, max_batch,
                                          min_bucket, bucket, storm_every):
    tr = _arrivals(arrivals, 301, seed=11)
    pol = BatchPolicy(mode=mode, max_batch=max_batch, max_wait_s=2e-3,
                      min_bucket=min_bucket, bucket=bucket)
    kw = dict(replica=3, writer=2, republish_every=storm_every,
              republish_n=5, service_model=SVC_BY_SIZE)
    want_log, got_log = CallLog(), CallLog()
    want = oracle_replay(want_log, tr, pol, **kw)
    got = replay(got_log, tr, pol, **kw)
    assert np.array_equal(got.latency_s, want.latency_s)
    for field in ("t_end", "batch_sizes", "padded_sizes", "fires", "walls",
                  "events"):
        assert getattr(got, field) == getattr(want, field), field
    assert got_log.calls == want_log.calls
    assert all(type(k) is int for e in got.events if e[0] != "fence"
               for k in e[1])
    assert all(type(c[1]) is list and all(type(k) is str for k in c[1])
               for c in got_log.calls if c[0] == "read")


def test_oracle_cases_reach_every_fire_kind():
    """The equivalence cases above are not all full waves: deadline and
    final fires, partial pads and storms all occur among them."""
    kinds = collections.Counter()
    for mode in ("continuous", "fixed"):
        for arrivals in ("backlog", "poisson", "burst"):
            res = replay(CallLog(), _arrivals(arrivals, 301, seed=11),
                         BatchPolicy(mode=mode, max_batch=12,
                                     max_wait_s=2e-3),
                         service_model=SVC_BY_SIZE)
            kinds.update({k: v for k, v in res.fires.items() if v})
            kinds["partial"] += sum(b < 12 for b in res.batch_sizes)
    assert set(kinds) == {"full", "deadline", "final", "partial"}


class UncheckedTrace:
    """What ``replay`` reads of a trace, without ``RequestTrace``'s own
    validation (the serving benchmark brings a trace class of its own)."""

    def __init__(self, t, kid, n_keys):
        self.t, self.kid, self.n_keys = t, kid, n_keys

    def __len__(self):
        return len(self.t)


def test_replay_refuses_decreasing_arrivals():
    tr = UncheckedTrace(np.array([0.0, 2.0, 1.0]), np.zeros(3, np.int32), 1)
    with pytest.raises(ValueError):
        replay(FakeBackend(), tr, BatchPolicy(), service_model=SVC)


@pytest.mark.parametrize("storm_every", [0, 50])
def test_key_of_runs_once_per_distinct_key(storm_every):
    kid = np.random.default_rng(5).choice([3, 9, 17, 40, 41], size=500)
    tr = mk_trace(np.zeros(500), kid, n_keys=64)
    seen = collections.Counter()

    def key_of(k):
        assert type(k) is int
        seen[k] += 1
        return f"user{k}"

    log = CallLog()
    res = replay(log, tr, BatchPolicy(max_batch=64, min_bucket=8),
                 key_of=key_of, republish_every=storm_every, republish_n=4,
                 service_model=SVC)
    storm_ids = {k for e in res.events if e[0] == "write" for k in e[1]}
    assert set(seen) == set(kid.tolist()) | storm_ids
    assert set(seen.values()) == {1}
    reads = [c[1] for c in log.calls if c[0] == "read"]
    assert len(reads) == 8 and all(type(r) is list for r in reads)
    assert [k for r in reads for k in r][:500] == \
        [f"user{k}" for k in kid.tolist()]
    # every wave hands over the same string object for the same key
    assert len({id(k) for r in reads for k in r}) == len(set(kid.tolist()))
