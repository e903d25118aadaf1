"""Serving driver: open-loop replay of a seeded read stream, with publish
storms, through the program's scheduler against its lease fabric.

Set-up (timed as ``setup_s``): the fabric is built on the device; every
key is published once (so every key is interned and the TSU is full);
each power-of-two wave bucket is compiled, hit and miss, with the
traffic's storm and fence; then a warm-up replay from another seed at the
cell's own load fills the tiers.  The window's trace is drawn from
``--seed`` in set-up too: ``rate_rps x seconds`` Poisson arrivals, or for
a ``backlog`` mix, ``chunks`` chunks of ``chunk_reads`` reads all due at
once, replayed in turn until ``seconds`` of wall time have passed.
Python's cyclic garbage collector is paused over the window (reference
counting still frees): the records kept for the check would otherwise
make its full collections longer as the window goes on.

Every call into the fabric goes through ``Proxy``, which records it with
its result.  After the window the fabric is freed and the whole recorded
stream, set-up included, runs through the plain reference named by the
configuration: every read's result, every fence's clock and the final
stats block must be equal.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time

import numpy as np

from bench.lib import loadgen, spans, xplane
from bench.refs.lease_kv import LeaseKV

FABRIC_KEYS = ("n_shards", "tsu_capacity", "replica_sets", "replica_ways",
               "shared_sets", "shared_ways", "rd_lease", "wr_lease",
               "max_in_flight")


class _Handle:
    __slots__ = ("_finish",)

    def __init__(self, finish):
        self._finish = finish

    def result(self):
        return self._finish()


class Proxy:
    """The fabric as ``scheduler.replay`` sees it: forwards each call,
    records it with its result, and under ``--trace 1`` wraps it in a
    profiler annotation and stops the profiler at the first call after
    ``stop_at`` (perf_counter seconds)."""

    def __init__(self, fab, annotate=False):
        self.fab = fab
        self.calls = []
        self.annotate = annotate
        self.stop_at = None
        self.on_stop = None

    def _ann(self, name):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _tick(self):
        if self.stop_at is not None and time.perf_counter() >= self.stop_at:
            self.stop_at = None
            self.on_stop()

    def write_batch(self, items, replica=0):
        self._tick()
        items = list(items)
        self.calls.append(("write", items, replica))
        with self._ann("bench.write_batch"):
            self.fab.write_batch(items, replica=replica)

    def fence(self):
        self._tick()
        with self._ann("bench.fence"):
            g = self.fab.fence()
        self.calls.append(("fence", g))
        return g

    def read_batch_async(self, keys, replica=0):
        self._tick()
        call = ["read", keys, replica, None]
        self.calls.append(call)
        with self._ann("bench.read_dispatch"):
            h = self.fab.read_batch_async(keys, replica=replica)

        def finish():
            if call[3] is None:
                with self._ann("bench.read_result"):
                    call[3] = h.result()
            return call[3]

        return _Handle(finish)

    def read_batch(self, keys, replica=0):
        return self.read_batch_async(keys, replica).result()


def build_fabric(config, devices):
    from repro.coherence.fabric import (ArrayFabric, FabricConfig,
                                        ShardedArrayFabric)

    cfg = FabricConfig(**{k: config[k] for k in FABRIC_KEYS})
    kw = dict(n_nodes=config["n_nodes"],
              replicas_per_node=config["replicas_per_node"])
    if config["backend"] == "ArrayFabric":
        return ArrayFabric(cfg, **kw)
    if config["backend"] == "ShardedArrayFabric":
        from repro.launch.mesh import make_fabric_mesh

        mesh = make_fabric_mesh(n_shards=cfg.n_shards,
                                devices=devices[:config["chips"]])
        if int(mesh.devices.size) != config["chips"]:
            raise RuntimeError(f"{cfg.n_shards} shards do not spread over "
                               f"{config['chips']} devices")
        return ShardedArrayFabric(cfg, mesh=mesh, **kw)
    raise ValueError(f"unknown backend {config['backend']!r}")


def reference(config) -> LeaseKV:
    return LeaseKV(n_nodes=config["n_nodes"],
                   replicas_per_node=config["replicas_per_node"],
                   **{k: config[k] for k in FABRIC_KEYS})


def key_of(traffic):
    fmt = traffic["key_format"]
    return lambda k: fmt.format(k)


def policy(traffic):
    from repro.runtime import scheduler

    return scheduler.BatchPolicy(mode="continuous",
                                 max_batch=traffic["max_batch"],
                                 max_wait_s=traffic["max_wait_s"],
                                 min_bucket=traffic["min_bucket"])


def _replay(proxy, trace, config, traffic):
    from repro.runtime import scheduler

    return scheduler.replay(
        proxy, trace, policy(traffic), replica=config["reader"],
        writer=config["writer"], key_of=key_of(traffic),
        republish_every=traffic["storm_every"],
        republish_n=traffic["storm_n"])


def window_traces(traffic, seed, seconds):
    """The window's traces, drawn from seed: a backlog mix's chunks, or
    the one trace of ``seconds`` of arrivals."""
    def draw(n, s):
        return loadgen.synthesize(n, traffic["keys"], a=traffic["zipf_a"],
                                  process=traffic["process"],
                                  rate=traffic.get("rate_rps", 1.0), seed=s)

    if traffic["process"] == "backlog":
        return [draw(traffic["chunk_reads"], [seed, i])
                for i in range(traffic["chunks"])]
    return [draw(int(round(traffic["rate_rps"] * seconds)), seed)]


def reaches_miss_pass(config, traffic) -> bool:
    """Whether the window's waves can miss the reader's tier: the mix
    publishes, or holds more keys than the tier has lines."""
    return bool(traffic["storm_every"]) or \
        traffic["keys"] > config["replica_sets"] * config["replica_ways"]


def _pow2s(lo, hi):
    b = lo
    while b <= hi:
        yield b
        b *= 2


def _spread(pool, n, place):
    """Take up to ``n`` keys from ``pool`` no two of which share a replica
    set or a shared set (``place`` gives a key's sets): a miss wave of
    them needs one round, so ``d`` copies of the first make a wave of
    exactly ``d`` rounds."""
    taken, s1s, s2s, rest = [], set(), set(), []
    for k in pool:
        s1, s2, _ = place(k)
        if len(taken) < n and s1 not in s1s and s2 not in s2s:
            taken.append(k)
            s1s.add(s1)
            s2s.add(s2)
        else:
            rest.append(k)
    pool[:] = rest
    return taken


def warm_up(proxy, config, traffic, seed):
    """Publish the keys from ``publish_from`` on (so the TSU starts full
    of records the window's storms do not write) and read the others once
    (so every key is known to the fabric before the window).  Then
    compile the shapes this traffic reaches: the fast read at every wave
    bucket, hit and miss, with the mix's storm and fence; where the
    window can miss (``reaches_miss_pass``), the op-scan that a wave of
    one missing key cycled over a bucket falls back to (deadline-fired
    waves of a request or two, padded) and the miss pass at every
    (lanes, rounds) bucket its round budget allows, on keys the reader
    has not read; and replay ``warmup_reads`` of the cell's own traffic
    from another seed.
    Returns the host seconds of each of the four phases."""
    key = key_of(traffic)
    n_keys, w, r = traffic["keys"], config["writer"], config["reader"]
    storm_n = min(traffic["storm_n"] or n_keys, n_keys)
    t = [time.perf_counter()]
    top = traffic["max_batch"]
    first = traffic["publish_from"]
    misses = reaches_miss_pass(config, traffic)
    for lo in range(first, n_keys, storm_n):
        proxy.write_batch([(key(k), f"p{lo}") for k in
                           range(lo, min(lo + storm_n, n_keys))], replica=w)
        proxy.fence()
    for lo in range(0, first, top):              # intern the rest: reads
        proxy.read_batch([key(k) for k in range(lo, min(lo + top, first))],
                         replica=r)
    t.append(time.perf_counter())
    fresh = iter(range(n_keys - 1, -1, -1))     # keys the reader never read
    for b in _pow2s(traffic["min_bucket"], top):
        if traffic["storm_every"]:
            proxy.write_batch([(key(k), f"w{b}") for k in range(storm_n)],
                              replica=w)
            proxy.fence()
        if misses:
            proxy.read_batch([key(next(fresh))] * b, replica=r)
        ks = [key(k % n_keys) for k in range(b)]
        proxy.read_batch(ks, replica=r)            # misses
        proxy.read_batch(ks, replica=r)            # hits
    t.append(time.perf_counter())
    if misses:
        pool = [key(k) for k in fresh]
        place = reference(config).place
        for m in _pow2s(32, top):
            for rounds in _pow2s(4, max(6, m // 4)):
                ks = _spread(pool, m - rounds + 1, place)
                proxy.read_batch([ks[0]] * rounds + ks[1:], replica=r)
    t.append(time.perf_counter())
    n = traffic["warmup_reads"]
    if n:
        trace = loadgen.synthesize(
            n, n_keys, a=traffic["zipf_a"], process=traffic["process"],
            rate=traffic.get("rate_rps", 1.0), seed=[seed, 1 << 40])
        _replay(proxy, trace, config, traffic)
    t.append(time.perf_counter())
    return dict(zip(("publish_s", "buckets_s", "miss_rounds_s", "replay_s"),
                    (b - a for a, b in zip(t, t[1:]))))


def check(config, calls, fab_stats):
    """Replay the recorded calls through the plain reference; returns the
    counts that must be 0 and the number of reads compared."""
    ref = reference(config)
    bad_reads = bad_fences = reads = 0
    for call in calls:
        if call[0] == "read":
            want = ref.read_batch(call[1], call[2])
            got = call[3]
            reads += len(want)
            bad_reads += sum(a != b for a, b in zip(got, want)) + \
                abs(len(got) - len(want))
        elif call[0] == "write":
            ref.write_batch(call[1], call[2])
        else:
            bad_fences += ref.fence() != call[1]
    want = ref.stats()
    bad_stats = sum(fab_stats.get(k) != v for k, v in want.items()) + \
        len(set(fab_stats) - set(want))
    return {"read_mismatches": bad_reads, "fence_mismatches": bad_fences,
            "stats_mismatches": bad_stats}, reads


def _peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(cell, *, seed, seconds, trace, t0, devices, meter):
    import jax

    from repro.obs import trace as obs

    config, traffic = cell["config"], cell["traffic"]
    devices = devices[:config["chips"]]
    fab = build_fabric(config, devices)
    proxy = Proxy(fab, annotate=bool(trace))
    phases = warm_up(proxy, config, traffic, seed)
    n_setup_calls = len(proxy.calls)

    traces = window_traces(traffic, seed, seconds)
    gc.collect()
    gc.freeze()
    prof = {}
    tracer = obs.get_tracer()
    if trace:
        tracer.clear()
        obs.enable()
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        prof["before"] = fab.stats()

        def stop():
            prof["after"] = fab.stats()
            jax.profiler.stop_trace()

        proxy.on_stop = stop
        jax.profiler.start_trace(logdir)
        proxy.stop_at = time.perf_counter() + traffic["trace_seconds"]
    stats0 = fab.stats()
    gc.disable()
    c0 = meter.snapshot()
    setup_s = time.perf_counter() - t0
    w0 = time.perf_counter()
    results = []
    while True:
        tr = traces[len(results) % len(traces)]
        results.append(_replay(proxy, tr, config, traffic))
        if traffic["process"] != "backlog" or \
                time.perf_counter() - w0 >= seconds:
            break
    stats1 = fab.stats()                 # waits for the device
    wall_s = time.perf_counter() - w0
    gc.enable()
    gc.unfreeze()
    c1 = meter.snapshot()
    compiles = c1[0] - c0[0] + c1[2] - c0[2]     # compiled or loaded
    if trace:
        obs.disable()
        if "after" not in prof:
            stop()
        devs, host, progs = xplane.load(xplane.find_xplane(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        red = xplane.reduce_trace(devs, host, progs)
    peak = _peak_bytes(devices)
    calls = proxy.calls
    del proxy, fab
    gc.collect()

    lat = np.concatenate([r.latency_s for r in results])
    n_reads = int(lat.size)
    storms = sum(ev[0] == "fence" for r in results for ev in r.events)
    clock_s = sum(r.t_end for r in results)
    e2e = {"read_p99_ms": float(np.percentile(lat, 99)) * 1e3,
           "storm_ms": (sum(r.walls["republish_s"] for r in results)
                        / storms * 1e3) if storms else None,
           "reads_per_s": n_reads / wall_s}
    waves = sum(len(r.batch_sizes) for r in results)
    delta = {k: stats1[k] - stats0[k] for k in stats1}
    counters = {
        "reads": n_reads, "waves": waves, "storms": storms,
        "mean_wave": n_reads / waves,
        "waves_with_misses": waves - delta["fast_read_batches"],
        "window_stats": delta, "replay_clock_s": clock_s,
        "window_wall_s": wall_s, "compiles_in_window": compiles}
    t_check = time.perf_counter()
    checks, compared = check(config, calls, stats1)
    check_s = time.perf_counter() - t_check
    ctx = {"counters": counters, "config": config, "traffic": traffic,
           "device_kind": devices[0].device_kind}
    if trace:
        ctx["spans"] = spans.totals(tracer.events)
        tracer.clear()
        ctx["trace"] = red
        d = {k: prof["after"][k] - prof["before"][k] for k in prof["after"]}
        # logical requests in the traced window: every read of every
        # wave is probed by the fast read
        ctx["work"] = {"fast_read": [(d["reads"],
                                      (config["replica_ways"],))]}
        counters["traced_reads"] = d["reads"]
    bad = checks["read_mismatches"]
    return {
        "setup_s": setup_s, "e2e": e2e, "ctx": ctx,
        "attempted": n_reads, "failed": bad,
        "checks": {k: {"value": v, "limit": 0} for k, v in checks.items()},
        "memory_peak_bytes": peak,
        "info": {"compiles_in_window": compiles, "reads_compared": compared,
                 "setup_compiles": c0[0], "setup_compile_s": c0[1],
                 "setup_phases": phases,
                 "setup_calls": n_setup_calls, "window_wall_s": wall_s,
                 "replay_clock_s": clock_s, "check_s": check_s,
                 "waves": waves, "storms": storms,
                 "fast_read_batches": delta["fast_read_batches"],
                 "tsu_evictions": delta["tsu_evictions"],
                 "l1_hits": delta["l1_hits"],
                 "program_runs": red["program_runs"] if trace else None,
                 "traced_reads": counters.get("traced_reads")},
    }
