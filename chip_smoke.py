"""One-process smoke run of the lease service, its publish storms and the
figure engine on a TPU, with every result checked.

    python chip_smoke.py              # one chip: phases a, b and c
    python chip_smoke.py --chips 4    # only the sharded fabric over four
                                      # chips, against a one-device fabric

Phases (importable functions that take their sizes, so tests run them
small on the CPU):

  a. kernel parity: ``lease_probe``, ``miss_round`` and ``write_grant`` on
     seeded inputs at the shapes of phases b and c equal ``kernels.ref``;
  b. served lease reads plus publish storms on ``ArrayFabric`` at a
     deployment size (8 TSU shards x 4096 entries), replayed open-loop by
     ``scheduler.replay``; the served event stream is then applied to the
     ``HostFabric`` oracle, and every per-read result and the stats block
     must be identical;
  c. the figure engine: ``engine.sweep`` of SM-WT-C-HALCONE over the 11
     standard benchmarks at 4 GPUs x 32 CUs; every simulated counter must
     equal the CPU's (``ENGINE_EXPECTED``) and ``inval_msgs`` must be 0.

On one chip it also checks that the compiled fast read, miss pass, write
pass and engine step each hold a Pallas kernel (``tpu_custom_call``).  With
``--chips 4`` phase b's stream runs on ``ShardedArrayFabric`` (8 shards
over the four chips) and on a one-device ``ArrayFabric``: results and
stats must be identical, and the per-batch TSU exchange must compile to
exactly one all-gather.

The script exits non-zero, printing no result, unless JAX's first device
is a TPU.  Earlier lines print the device kind, each phase's compile
seconds and counts; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# phase b's deployment: 8 TSU shards x 4096 entries (32,768 in all), a
# replica tier of 1024 sets x 4 ways and a shared tier of 2048 sets x 8
# ways; one node with a writer replica (0) and a reader replica (1)
FABRIC = dict(n_shards=8, tsu_capacity=4096, replica_sets=1024,
              replica_ways=4, shared_sets=2048, shared_ways=8)
TRAFFIC = dict(n_reads=16384, n_keys=65536, zipf_a=0.99, max_batch=1024,
               storm_every=2048, storm_n=512)
WRITER, READER = 0, 1
RATE_RPS = 250_000.0            # offered load of the synthesized trace


def service_model(n: int) -> float:
    """The replay's virtual clock charges each fabric call this many
    seconds, so wave formation (and with it the whole served stream) is
    the same on every backend and every run."""
    return 2e-4 + 1e-6 * n


ENGINE_ROUNDS = 256             # the --mini figure suite's trace length
# engine.sweep counters of SM-WT-C-HALCONE at 4 GPUs x 32 CUs and
# ENGINE_ROUNDS rounds, recorded from a CPU run (the counters are exact
# integers): {benchmark: counter values in engine.COUNTERS order}
ENGINE_EXPECTED = {
    "aes": (19251, 17451, 5325, 1800, 0, 0, 0, 0, 0, 18395, 6181, 1232064,
            1116864, 0),
    "atax": (23558, 11207, 5114, 12351, 0, 0, 0, 0, 0, 25817, 2855, 1507712,
             717248, 0),
    "bfs": (27584, 11378, 1088, 16206, 0, 0, 0, 0, 0, 24325, 4347, 1765376,
            728192, 0),
    "bicg": (20295, 9828, 4281, 10467, 0, 0, 0, 0, 0, 22134, 2442, 1298880,
             628992, 0),
    "bs": (27499, 21845, 1173, 5674, 218, 2487, 0, 0, 0, 14395, 14277,
           1759936, 1398080, 0),
    "fir": (21054, 15703, 7618, 5351, 0, 0, 0, 0, 0, 19416, 9256, 1347456,
            1004992, 0),
    "fws": (26219, 18287, 2453, 7960, 407, 6836, 0, 0, 0, 19405, 9267,
            1678016, 1170368, 0),
    "mm": (13825, 2447, 14847, 11378, 0, 0, 0, 0, 0, 27207, 1465, 884800,
           156608, 0),
    "mp": (20090, 16647, 4486, 3443, 0, 0, 0, 0, 0, 18488, 6088, 1285760,
           1065408, 0),
    "rl": (27274, 24744, 1398, 2530, 0, 0, 0, 0, 0, 14403, 14269, 1745536,
           1583616, 0),
    "conv": (16144, 4500, 12528, 11644, 0, 0, 0, 0, 0, 25170, 3502, 1033216,
             288000, 0),
}


# ------------------------------------------------------------ a. kernels
def _same(name, got, want):
    for i, (g, w) in enumerate(zip(got, want)):
        if not np.array_equal(np.asarray(g), np.asarray(w)):
            raise AssertionError(f"{name}: output {i} differs from "
                                 "kernels.ref")


def phase_kernels(lanes=1024, write_lanes=512, tsu_capacity=4096,
                  replica_ways=4, shared_ways=8, cus=128, l1_ways=4,
                  l2_ways=16, seed=0) -> dict:
    """The three lease kernels against their ``kernels.ref`` oracles on
    seeded inputs: ``lease_probe`` at the fast read's widest batch, the
    op-scan's single lane and the engine's L1/L2 probes; ``miss_round`` at
    the miss pass's widest batch over a whole TSU shard row;
    ``write_grant`` at the publish storm's width.  Returns the number of
    kernel calls checked."""
    import jax.numpy as jnp

    from repro.core.protocol import TS_MAX
    from repro.kernels import ref
    from repro.kernels.lease_probe import lease_probe
    from repro.kernels.tier_pass import miss_round, write_grant

    rng = np.random.default_rng(seed)

    def r(lo, hi, *shape):
        return jnp.asarray(rng.integers(lo, hi, shape).astype(np.int32))

    n = 0
    for m, w in ((lanes, replica_ways), (1, replica_ways),
                 (1, shared_ways), (cus, l1_ways), (cus, l2_ways)):
        args = (r(-1, 2 * w, m, w), r(0, 40, m, w), r(0, 40, m),
                r(0, 2 * w, m), r(0, 40, m), r(40, 50, m))
        _same(f"lease_probe[{m}x{w}]", lease_probe(*args),
              ref.lease_probe_ref(*args))
        n += 1
    C = tsu_capacity
    args = (r(-1, 2 * replica_ways, lanes, replica_ways),
            r(0, 40, lanes, replica_ways),
            r(-1, 2 * shared_ways, lanes, shared_ways),
            r(0, 40, lanes, shared_ways), r(0, 40, lanes, shared_ways),
            r(-1, C, lanes, C), r(0, TS_MAX + 8, lanes, C), r(0, 40, lanes),
            r(0, 40, lanes), r(0, C, lanes), r(0, 2, lanes),
            jnp.full((lanes,), 8, jnp.int32))
    _same(f"miss_round[{lanes}x{C}]", miss_round(*args),
          ref.miss_round_ref(*args))
    args = (r(-1, C, write_lanes, C), r(0, TS_MAX + 8, write_lanes, C),
            r(0, 2 * C, write_lanes, C), r(0, C, write_lanes),
            r(1, 10, write_lanes))
    _same(f"write_grant[{write_lanes}x{C}]", write_grant(*args),
          ref.write_grant_ref(*args))
    return {"kernel_calls_checked": n + 2}


# ------------------------------------------------------ b. served stream
class _Recorder:
    """Forwards the calls ``scheduler.replay`` makes to a fabric and keeps
    what the oracle needs to replay them: each write batch's items and
    each read batch's results."""

    def __init__(self, fab):
        self.fab = fab
        self.calls: list = []

    def write_batch(self, items, replica=0):
        items = list(items)
        self.calls.append(("write", items, replica))
        self.fab.write_batch(items, replica=replica)

    def fence(self):
        self.calls.append(("fence",))
        return self.fab.fence()

    def read_batch_async(self, keys, replica=0):
        from repro.coherence.fabric.backend import ReadBatchHandle

        call = ["read", list(keys), replica, None]
        self.calls.append(call)
        handle = self.fab.read_batch_async(keys, replica=replica)

        def finish():
            call[3] = handle.result()
            return call[3]

        return ReadBatchHandle(finish)


def _key(k: int) -> str:
    return f"prefix/{k}"


def serve_stream(fab, n_reads, n_keys, zipf_a, max_batch, storm_every,
                 storm_n, seed=0) -> dict:
    """Replay a bounded-Zipf read stream open-loop against ``fab`` with a
    continuous batch policy, a ``storm_n``-key republish storm plus fence
    every ``storm_every`` served requests.  Returns the served events,
    the recorded calls and the fabric's stats."""
    from repro.runtime import loadgen, scheduler

    trace = loadgen.synthesize(n_reads, n_keys, a=zipf_a, rate=RATE_RPS,
                               seed=seed)
    rec = _Recorder(fab)
    res = scheduler.replay(
        rec, trace, scheduler.BatchPolicy(mode="continuous",
                                          max_batch=max_batch),
        replica=READER, writer=WRITER, key_of=_key,
        republish_every=storm_every, republish_n=storm_n,
        service_model=service_model)
    return {"events": res.events, "calls": rec.calls, "stats": fab.stats(),
            "waves": len(res.batch_sizes)}


def check_against_host(cfg, served) -> None:
    """Apply the served event stream to the ``HostFabric`` oracle: every
    read batch's results and the final stats block must be identical."""
    from repro.coherence.fabric import HostFabric

    host = HostFabric(cfg, n_nodes=1, replicas_per_node=2)
    calls = iter(served["calls"])
    for i, ev in enumerate(served["events"]):
        call = next(calls)
        if call[0] != ev[0]:
            raise AssertionError(f"event {i}: {ev[0]} served as {call[0]}")
        if ev[0] == "read":
            keys = [_key(k) for k in ev[1]]
            if keys != call[1]:
                raise AssertionError(f"event {i}: read keys differ")
            if host.read_batch(keys, replica=call[2]) != call[3]:
                raise AssertionError(f"event {i}: read results differ "
                                     "from HostFabric")
        elif ev[0] == "write":
            if [_key(k) for k in ev[1]] != [k for k, _ in call[1]]:
                raise AssertionError(f"event {i}: write keys differ")
            host.write_batch(call[1], replica=call[2])
        else:
            host.fence()
    _same_stats("HostFabric", host.stats(), served["stats"])


def _same_stats(name, want, got) -> None:
    diff = {k: (got.get(k), v) for k, v in want.items() if got.get(k) != v}
    if diff or set(want) != set(got):
        raise AssertionError(f"stats differ from {name} (got, want): {diff}")


def stream_counts(served) -> dict:
    s = served["stats"]
    return {"reads": s["reads"], "replica_hits": s["l1_hits"],
            "misses": s["reads"] - s["l1_hits"],
            "tsu_evictions": s["tsu_evictions"],
            "write_batches": s["write_batches"],
            "fast_read_batches": s["fast_read_batches"],
            "inval_msgs": s["inval_msgs"], "waves": served["waves"]}


def phase_fabric(fabric=FABRIC, traffic=TRAFFIC, seed=0) -> dict:
    """Served lease reads plus publish storms on ``ArrayFabric``, checked
    against the ``HostFabric`` oracle.  Returns the stream's counts."""
    from repro.coherence.fabric import ArrayFabric, FabricConfig

    cfg = FabricConfig(**fabric)
    fab = ArrayFabric(cfg, n_nodes=1, replicas_per_node=2)
    served = serve_stream(fab, seed=seed, **traffic)
    check_against_host(cfg, served)
    return stream_counts(served)


# ------------------------------------------------------- c. figure engine
def phase_engine(benches=None, rounds=ENGINE_ROUNDS, n_gpus=4,
                 cus_per_gpu=32, expected=None) -> dict:
    """``engine.sweep`` of SM-WT-C-HALCONE over the standard benchmarks.
    Every counter must be an exact integer, ``inval_msgs`` 0, and — when
    ``expected`` is given — each benchmark's counters equal to it.
    Returns ``{benchmark: [counters in engine.COUNTERS order]}``."""
    from repro.core import engine, traces
    from repro.core.sysconfig import sm_wt_halcone

    cfg = sm_wt_halcone(n_gpus=n_gpus, cus_per_gpu=cus_per_gpu)
    benches = list(benches or traces.STANDARD)
    ops, addrs = traces.pack_batch(
        [traces.standard_trace(cfg, traces.STANDARD[b], rounds)
         for b in benches])
    res = engine.sweep([cfg], ops, addrs)
    got = {}
    for i, b in enumerate(benches):
        vals = [float(res["counters"][k][0, i]) for k in engine.COUNTERS]
        if any(v != int(v) for v in vals):
            raise AssertionError(f"{b}: a counter is not an integer")
        got[b] = [int(v) for v in vals]
        if got[b][engine.COUNTERS.index("inval_msgs")]:
            raise AssertionError(f"{b}: HALCONE sent invalidations")
        if expected is not None and got[b] != list(expected[b]):
            raise AssertionError(f"{b}: counters differ from the CPU's: "
                                 f"{got[b]} != {list(expected[b])}")
    return got


# -------------------------------------------------- compiled-kernel check
def _abstract(tree):
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def compiled_kernels(fabric=FABRIC, lanes=1024, rounds=4,
                     engine_rounds=8) -> dict:
    """Whether the compiled fast read, miss pass, write pass and engine
    step each hold a Pallas kernel (``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp

    from repro.coherence.fabric import ArrayFabric, FabricConfig
    from repro.coherence.fabric import arrays as A
    from repro.core import engine
    from repro.core.sysconfig import sm_wt_halcone, stack_configs

    fab = ArrayFabric(FabricConfig(**fabric), n_nodes=1, replicas_per_node=2)
    af = _abstract(fab._af)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    mask = jax.ShapeDtypeStruct((rounds, lanes), jnp.bool_)
    s = jnp.int32(0)
    lowered = {
        "fast_read": A._build_fast_read(None).lower(
            af.rp, af.rp_gseq, af.rp_tick, af.g, af.r, i32(64), i32(lanes),
            s),
        "miss_pass": fab._miss_run.lower(af, i32(4, lanes), mask, s, s, s,
                                         s),
        "write_pass": fab._write_run.lower(af, i32(4, lanes), i32(7, lanes),
                                           mask, s, s, s, s, s),
    }
    cfg = sm_wt_halcone()
    ops = i32(1, engine_rounds, cfg.n_cus)
    lowered["engine_step"] = engine._sweep_run.lower(
        (stack_configs([cfg]),), ops, ops, n_addr=1024)
    out = {}
    for name, low in lowered.items():
        text = low.compile().as_text() or low.as_text()
        out[name] = "tpu_custom_call" in text
    return out


# ----------------------------------------------------- --chips 4: sharded
def count_all_gathers(hlo_text: str) -> int:
    """All-gather instructions in an HLO module (the async start of one on
    the TPU, the plain op on the CPU)."""
    return len(re.findall(r" all-gather(?:-start)?\(", hlo_text))


def phase_sharded(n_chips=4, fabric=FABRIC, traffic=TRAFFIC,
                  seed=0) -> dict:
    """Phase b's stream on ``ShardedArrayFabric`` (the TSU shards over
    ``n_chips`` devices) and on a one-device ``ArrayFabric``: per-read
    results and stats must be identical, and the per-batch TSU exchange
    must compile to exactly one all-gather."""
    import jax

    from repro.coherence.fabric import (ArrayFabric, FabricConfig,
                                        ShardedArrayFabric)
    from repro.launch.mesh import make_fabric_mesh

    cfg = FabricConfig(**fabric)
    mesh = make_fabric_mesh(n_shards=cfg.n_shards,
                            devices=jax.devices()[:n_chips])
    if mesh.devices.size != n_chips:
        raise AssertionError(f"{cfg.n_shards} shards cannot spread over "
                             f"{n_chips} devices")
    sharded = ShardedArrayFabric(cfg, n_nodes=1, replicas_per_node=2,
                                 mesh=mesh)
    af = sharded._af
    hlo = sharded._gather_run.lower(
        af.tsu, af.tsu_ver, af.tsu_gseq, af.tsu_seq,
        af.tsu_nseq).compile().as_text()
    gathers = count_all_gathers(hlo)
    if gathers != 1:
        raise AssertionError(f"the TSU exchange holds {gathers} all-gathers")
    a = serve_stream(sharded, seed=seed, **traffic)
    b = serve_stream(ArrayFabric(cfg, n_nodes=1, replicas_per_node=2),
                     seed=seed, **traffic)
    if [c[3] for c in a["calls"] if c[0] == "read"] != \
            [c[3] for c in b["calls"] if c[0] == "read"]:
        raise AssertionError("sharded read results differ from one device")
    _same_stats("the one-device ArrayFabric", b["stats"], a["stats"])
    return {**stream_counts(a), "devices": int(mesh.devices.size),
            "exchange_all_gathers": gathers}


# ------------------------------------------------------------------ main
class _CompileMeter:
    """Seconds JAX spent compiling (persistent-cache reads included) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.secs = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def run(self, name, fn, *args, **kw):
        c0, h0, t0 = self.secs, self.hits, time.perf_counter()
        out = fn(*args, **kw)
        print(json.dumps({
            "phase": name, "wall_s": round(time.perf_counter() - t0, 3),
            "compile_s": round(self.secs - c0, 3),
            "cache_hits": self.hits - h0, "result": out}), flush=True)
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded fabric over four chips")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    print(json.dumps({"device_kind": devs[0].device_kind,
                      "devices": len(devs),
                      "compile_cache": enable_compile_cache()}), flush=True)
    meter = _CompileMeter()
    if args.chips == 4:
        meter.run("sharded", phase_sharded, n_chips=4)
    else:
        meter.run("a_kernels", phase_kernels)
        meter.run("b_fabric", phase_fabric)
        meter.run("c_engine", phase_engine, expected=ENGINE_EXPECTED)
        found = meter.run("compiled_kernels", compiled_kernels)
        if not all(found.values()):
            raise AssertionError(f"no tpu_custom_call in {found}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
