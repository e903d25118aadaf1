"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time, per-operation device time, and the
idle gaps between device operations, each attributed to what the host was
doing then.

``load(path)`` reads the trace with ``jax.profiler.ProfileData`` into
plain tuples; ``reduce_trace`` works on those tuples alone, so tests feed
it small synthetic traces.

  * A device is a plane named ``/device:...``; its operations are the
    events of its ``XLA Ops`` line, each labelled with its HLO instruction
    and result type (``%write_grant.9 = s32[7,512]``).  A loop's event
    spans the events of its body, so per-label sums overlap; busy time is
    a union and counts each instant once.
  * Its programs are the events of its ``XLA Modules`` line, one per run
    of a compiled program, named after the jitted function
    (``jit_fast(1023...)`` gives ``jit_fast``).  A program's event holds
    every operation of that run, the copies that stage a kernel's
    operands into on-chip memory included.
  * Host activity is the events of the host plane whose names start with
    ``HOST_PREFIX``: the annotations the benchmark puts around each call
    into the program.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Sequence, Tuple

HOST_PREFIX = "bench."
OP_LINES = ("XLA Ops",)
PROGRAM_LINE = "XLA Modules"
LABEL_LEN = 96
IDLE_HOST = "host:outside_calls"

Event = Tuple[str, float, float]          # (label, start_ns, dur_ns)


def find_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(files)}")
    return files[0]


def _label(ev) -> str:
    """The event's HLO text up to its result shape's layout, e.g.
    ``%write_grant.9 = s32[7,512]``."""
    return ev.name.split("{", 1)[0][:LABEL_LEN]


def load(path: str):
    """({device plane name: [Event]}, [host Event], {device plane name:
    [program Event]}) from an xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    programs: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = [(_label(e), float(e.start_ns), float(e.duration_ns))
                   for line in plane.lines if line.name in OP_LINES
                   for e in line.events]
            if evs:
                devices[plane.name] = evs
            programs[plane.name] = [
                (e.name.split("(", 1)[0], float(e.start_ns),
                 float(e.duration_ns))
                for line in plane.lines if line.name == PROGRAM_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            host += [(e.name, float(e.start_ns), float(e.duration_ns))
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST_PREFIX)]
    return devices, host, programs


def union(intervals: Sequence[Tuple[float, float]]):
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _split_gap(host_iv, starts, lo, hi, into):
    """Add the gap ``[lo, hi)`` to ``into`` by the host annotations that
    overlap it (calls into the program, one after another, never
    nested); the rest of the gap goes to ``IDLE_HOST``."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    covered = 0.0
    while i < len(host_iv) and host_iv[i][1] < hi:
        name, s, e = host_iv[i]
        part = min(e, hi) - max(s, lo)
        if part > 0:
            into[name] += part / 1e9
            covered += part
        i += 1
    into[IDLE_HOST] += (hi - lo - covered) / 1e9


def reduce_trace(devices: Dict[str, List[Event]], host: List[Event],
                 programs: Dict[str, List[Event]] = None,
                 window: Tuple[float, float] = None) -> dict:
    """Busy seconds (mean over devices), window seconds, device seconds
    per operation label and per program, and runs per program (summed
    over devices), and the idle time of the lead device (sorted plane
    name first) split by the host annotations that overlap each gap.
    The window defaults to the span of all events, device and host."""
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "programs": {},
                "program_runs": {}, "idle_by_host": {}, "n_devices": 0}
    if window is None:
        evs = [ev for evs in devices.values() for ev in evs] + list(host)
        window = (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))
    lo, hi = window
    busy = []
    ops: Dict[str, float] = collections.defaultdict(float)
    for evs in devices.values():
        iv = union(_clip([(s, s + d) for _, s, d in evs], lo, hi))
        busy.append(sum(e - s for s, e in iv))
        for label, s, d in evs:
            if s >= lo and s < hi:
                ops[label] += d / 1e9
    progs: Dict[str, float] = collections.defaultdict(float)
    runs: Dict[str, int] = collections.defaultdict(int)
    for evs in (programs or {}).values():
        for name, s, d in evs:
            if s >= lo and s < hi:
                progs[name] += d / 1e9
                runs[name] += 1
    lead = union(_clip([(s, s + d) for _, s, d in
                        devices[sorted(devices)[0]]], lo, hi))
    gaps, t = [], lo
    for s, e in lead:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    host_iv = sorted(((n, s, s + d) for n, s, d in host),
                     key=lambda x: x[1])
    starts = [s for _, s, _ in host_iv]
    idle: Dict[str, float] = collections.defaultdict(float)
    for s, e in gaps:
        _split_gap(host_iv, starts, s, e, idle)
    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (hi - lo) / 1e9, "ops": dict(ops),
            "programs": dict(progs), "program_runs": dict(runs),
            "idle_by_host": dict(idle), "n_devices": len(devices)}


def top(d: Dict[str, float], n: int = 10):
    """The ``n`` largest entries as [[name, seconds], ...]."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
