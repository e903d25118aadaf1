"""Figure-engine driver: ``engine.sweep`` of the configuration's systems
over the traffic's benchmark traces, back to back.

Set-up (timed as ``setup_s``): the traces are generated from ``--seed``
(one process per benchmark, numpy only), then one sweep compiles the
engine's programs.  The window runs whole sweeps until ``seconds`` of
wall time have passed; ``sim_events_per_s`` counts every simulated CU
op slot (systems x benchmarks x rounds x CUs per sweep) over that wall
time.  Afterwards every sweep's counters must equal the first's, and the
first's must equal the plain reference's (``bench/refs/halcone_engine``,
one process per system) for every counter of every (system, benchmark).
"""
from __future__ import annotations

import concurrent.futures as cf
import multiprocessing
import os
import shutil
import tempfile
import time

import numpy as np

from bench.lib import engine_traces, spans, xplane
from bench.refs import halcone_engine


def systems(config):
    """One dict of every field per system: the configuration's common
    fields under each system's own."""
    return [{**config["common"], **s} for s in config["systems"]]


def _pool(n):
    return cf.ProcessPoolExecutor(
        max_workers=max(1, min(n, os.cpu_count() or 1)),
        mp_context=multiprocessing.get_context("spawn"))


def make_traces(config, traffic, seed):
    base = systems(config)[0]
    names = list(traffic["benchmarks"])
    with _pool(len(names)) as pool:
        futs = [pool.submit(engine_traces.standard_trace, base,
                            traffic["benchmarks"][b], traffic["rounds"],
                            [seed, i]) for i, b in enumerate(names)]
        return engine_traces.pack_batch([f.result() for f in futs])


def reference_counters(config, ops, addrs):
    """``[C, B, len(COUNTERS)]`` int64 counters of the plain reference."""
    sys_list = systems(config)
    with _pool(len(sys_list)) as pool:
        futs = [pool.submit(halcone_engine.simulate, s, ops, addrs)
                for s in sys_list]
        outs = [f.result() for f in futs]
    return np.stack([np.stack([o[k] for k in halcone_engine.COUNTERS], -1)
                     for o in outs])


def _matrix(res):
    return np.stack([np.asarray(res["counters"][k]).astype(np.int64)
                     for k in halcone_engine.COUNTERS], -1)


def run(cell, *, seed, seconds, trace, t0, devices, meter):
    import contextlib

    import jax

    from repro.core import engine
    from repro.core.sysconfig import SystemConfig
    from repro.obs import trace as obs

    config, traffic = cell["config"], cell["traffic"]
    cfgs = [SystemConfig(**s) for s in systems(config)]
    ops, addrs = make_traces(config, traffic, seed)
    first = _matrix(engine.sweep(cfgs, ops, addrs))      # compiles
    C, B, NC, R = len(cfgs), ops.shape[0], ops.shape[1], ops.shape[2]

    def ann():
        return jax.profiler.TraceAnnotation("bench.sweep") if trace \
            else contextlib.nullcontext()

    tracer = obs.get_tracer()
    if trace:
        tracer.clear()
        obs.enable()
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(logdir)
    c0 = meter.snapshot()
    setup_s = time.perf_counter() - t0
    w0 = time.perf_counter()
    results, traced = [], None
    while True:
        with ann():
            results.append(_matrix(engine.sweep(cfgs, ops, addrs)))
        now = time.perf_counter() - w0
        if trace and traced is None and now >= traffic["trace_seconds"]:
            traced = len(results)
            jax.profiler.stop_trace()
        if now >= seconds:
            break
    wall_s = time.perf_counter() - w0
    c1 = meter.snapshot()
    compiles = c1[0] - c0[0] + c1[2] - c0[2]     # compiled or loaded
    if trace:
        obs.disable()
        if traced is None:
            traced = len(results)
            jax.profiler.stop_trace()
        devs, host, progs = xplane.load(xplane.find_xplane(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        red = xplane.reduce_trace(devs, host, progs)
    peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:1]))

    t_check = time.perf_counter()
    want = reference_counters(config, ops, addrs)
    check_s = time.perf_counter() - t_check
    win = results[0]
    diverged = sum(not np.array_equal(r, win) for r in results)
    bad_sims = (win != want).any(-1) | np.stack(
        [(r != win).any(-1) for r in results]).any(0)
    n = len(results)
    ctx = {"counters": {"sweeps": n}, "config": config, "traffic": traffic,
           "device_kind": devices[0].device_kind}
    if trace:
        ctx["spans"] = spans.totals(tracer.events)
        tracer.clear()
        ctx["trace"] = red
        ctx["counters"]["sweeps_traced"] = traced
    return {
        "setup_s": setup_s,
        "e2e": {"sim_events_per_s": n * C * B * NC * R / wall_s},
        "ctx": ctx, "attempted": n * C * B, "failed": int(bad_sims.sum()),
        "checks": {
            "counter_mismatches": {"value": int((win != want).sum()),
                                   "limit": 0},
            "sweeps_differing": {"value": diverged, "limit": 0}},
        "memory_peak_bytes": peak,
        "info": {"compiles_in_window": compiles, "sweeps": n,
                 "setup_compiles": c0[0], "setup_compile_s": c0[1],
                 "differing_by_system": {
                     c.name: [int(bad_sims[i].sum()), sorted(
                         halcone_engine.COUNTERS[j] for j in np.nonzero(
                             (win[i] != want[i]).any(0))[0])]
                     for i, c in enumerate(cfgs)},
                 "window_wall_s": wall_s, "check_s": check_s,
                 "sweep_s": wall_s / n, "first_sweep_equal": bool(
                     np.array_equal(first, win)),
                 "simulations": C * B, "rounds": R, "cus": NC},
    }
