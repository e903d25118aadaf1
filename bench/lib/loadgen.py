"""Open-loop request traces for the serving cells: bounded-Zipf keys and
arrival processes, drawn from one seed.

A copy of the program's own generator (``repro.runtime.loadgen``), kept
here so that no later change to the program can change the traffic the
benchmark offers.  ``RequestTrace`` carries exactly what
``scheduler.replay`` reads from a trace: ``t``, ``kid``, ``n_keys`` and
its length.
"""
from __future__ import annotations

import dataclasses

import numpy as np


class BoundedZipf:
    """Exact truncated Zipf over ranks ``0..n-1``: pmf(k) ~ 1/(k+1)^a,
    sampled by inverse CDF (no unbounded tail folded back by a modulo)."""

    def __init__(self, n: int, a: float):
        if n < 1 or a <= 0:
            raise ValueError(f"need n >= 1 and a > 0, got n={n}, a={a}")
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(a)
        self._cdf = np.cumsum(w / w.sum())
        self._cdf[-1] = 1.0

    def sample(self, rng: np.random.Generator, size: int) -> np.ndarray:
        u = rng.random(size)
        return np.searchsorted(self._cdf, u, side="right").astype(np.int64)


def poisson_arrivals(rng, n: int, rate: float) -> np.ndarray:
    """Homogeneous Poisson arrivals at ``rate`` per second."""
    if rate <= 0:
        raise ValueError(f"need rate > 0, got {rate}")
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def backlog_arrivals(rng, n: int, rate: float) -> np.ndarray:
    """Every request due at time 0: a standing backlog (``rate`` unused)."""
    return np.zeros(n, np.float64)


PROCESSES = {"poisson": poisson_arrivals, "backlog": backlog_arrivals}


@dataclasses.dataclass(frozen=True)
class RequestTrace:
    """Request ``i`` asks for key ``kid[i]`` at ``t[i]`` seconds."""

    t: np.ndarray                 # [n] float64, nondecreasing
    kid: np.ndarray               # [n] int32 in [0, n_keys)
    n_keys: int

    def __len__(self) -> int:
        return len(self.t)


def synthesize(n_requests: int, n_keys: int, *, a: float, process: str,
               rate: float, seed: int) -> RequestTrace:
    """``n_requests`` keys from ``BoundedZipf(n_keys, a)`` with arrival
    times from the named process; the same seed gives the same trace."""
    if process not in PROCESSES:
        raise ValueError(f"unknown process {process!r}; one of "
                         f"{sorted(PROCESSES)}")
    rng = np.random.default_rng(seed)
    t = PROCESSES[process](rng, n_requests, rate)
    kid = BoundedZipf(n_keys, a).sample(rng, n_requests)
    return RequestTrace(t=np.asarray(t, np.float64),
                        kid=kid.astype(np.int32), n_keys=int(n_keys))
