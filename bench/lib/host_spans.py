"""The lead device's idle time in a profiler trace, split by the program's
spans and the harness's annotations on the host plane.

The program's ``repro.obs.trace`` spans, while enabled, are also
``jax.profiler`` annotations, so they sit on the host plane on the
device's clock.  Each instant of an idle gap of the lead device goes to
the innermost span or annotation open then (of those open, the one that
started last); an instant no span covers goes to ``UNSPANNED``.  The
window and the gaps are ``xplane.reduce_trace``'s (device events and
``bench.*`` annotations), so the parts sum to the idle time that
``device_idle`` reads.  A span still open when the profiler started or
stopped is not in the trace: its part of the window goes to whatever
encloses it there, or to ``UNSPANNED``.
"""
from __future__ import annotations

import bisect
import collections
import heapq
from typing import Dict, List

from bench.lib import xplane

PREFIXES = ("sched.", "fabric.", "engine.", "serve.", xplane.HOST_PREFIX)
UNSPANNED = "unspanned"


def load(path: str) -> List[xplane.Event]:
    """The host-plane events of an xplane file whose names are program
    spans or harness annotations, as (name, start_ns, dur_ns)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIXES)]


def _idle_gaps(devices: Dict[str, List[xplane.Event]],
               host: List[xplane.Event]):
    """``reduce_trace``'s window and the lead device's idle gaps in it,
    as sorted (start_ns, end_ns); ``host`` holds at least the ``bench.*``
    annotations that bound the window."""
    bench = [ev for ev in host if ev[0].startswith(xplane.HOST_PREFIX)]
    evs = [ev for evs in devices.values() for ev in evs] + bench
    lo = min(s for _, s, _ in evs)
    hi = max(s + d for _, s, d in evs)
    lead = xplane.union(xplane._clip(
        [(s, s + d) for _, s, d in devices[sorted(devices)[0]]], lo, hi))
    gaps, t = [], lo
    for s, e in lead:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return (lo, hi), gaps


def split(devices: Dict[str, List[xplane.Event]],
          host: List[xplane.Event]) -> Dict[str, float]:
    """Idle seconds of the lead device by the innermost span open, with
    ``UNSPANNED`` for the rest; empty when the trace has no device."""
    if not devices:
        return {}
    (lo, hi), gaps = _idle_gaps(devices, host)
    starts = [s for s, _ in gaps]
    cum = [0.0]
    for s, e in gaps:
        cum.append(cum[-1] + e - s)

    def idle_before(t):
        """Idle ns of the window before ``t``."""
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        s, e = gaps[i - 1]
        return cum[i - 1] + min(t, e) - s

    # sweep the span boundaries in time; at equal times ends go first
    points = sorted([(s, 1, i) for i, (_, s, _) in enumerate(host)] +
                    [(s + d, 0, i) for i, (_, s, d) in enumerate(host)])
    out: Dict[str, float] = collections.defaultdict(float)
    out[UNSPANNED] = 0.0
    open_, closed = [], set()           # heap of (-start, dur, index)
    t = lo
    for at, is_start, i in points + [(hi, 0, -1)]:
        at = min(max(at, lo), hi)
        while open_ and open_[0][2] in closed:
            heapq.heappop(open_)
        part = idle_before(at) - idle_before(t)
        if part > 0:
            out[host[open_[0][2]][0] if open_ else UNSPANNED] += part / 1e9
        t = at
        if i < 0:
            break
        if is_start:
            heapq.heappush(open_, (-host[i][1], host[i][2], i))
        else:
            closed.add(i)
    return dict(out)
