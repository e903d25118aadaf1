"""Per-name totals of the program's host spans (``repro.obs.trace``
events): count, inclusive seconds and self seconds, where a span's self
time is its duration less that of its direct children (the ``.device``
fences among them)."""
from __future__ import annotations

import collections
from typing import Dict, Iterable


def totals(events: Iterable) -> Dict[str, Dict[str, float]]:
    """``events``: ``(name, cat, tid, t0_ns, dur_ns, depth, args)``
    tuples.  Returns ``{name: {"count", "incl_s", "self_s"}}``."""
    by_tid = collections.defaultdict(list)
    for ev in events:
        by_tid[ev[2]].append(ev)
    out: Dict[str, Dict[str, float]] = {}
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e[3], e[5]))
        child = [0] * len(evs)
        stack = []                       # indexes of open ancestors
        for i, (_, _, _, t0, _, depth, _) in enumerate(evs):
            while stack and evs[stack[-1]][5] >= depth:
                stack.pop()
            if stack and evs[stack[-1]][5] == depth - 1:
                child[stack[-1]] += evs[i][4]
            stack.append(i)
        for i, (name, _, _, _, dur, _, _) in enumerate(evs):
            row = out.setdefault(name, {"count": 0, "incl_s": 0.0,
                                        "self_s": 0.0})
            row["count"] += 1
            row["incl_s"] += dur / 1e9
            row["self_s"] += (dur - child[i]) / 1e9
    return out
