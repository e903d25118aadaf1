"""Generative traces of the paper's 11 standard benchmarks (Table 3) for
the figure engine: a copy of the program's own generator
(``repro.core.traces.standard_trace``), kept here so that no later change
to the program can change the work the benchmark asks of the engine.
The benchmark models themselves are data, in the traffic file.

Op encoding: 0 nop, 1 read, 2 write, 3 fence, 4 compute (addr = cycles).
"""
from __future__ import annotations

import numpy as np

READ, WRITE, FENCE, COMPUTE = 1, 2, 3, 4


def standard_trace(system: dict, bench: dict, rounds: int, seed):
    """``([NC, rounds], [NC, rounds])`` int32 ops and addresses of one
    benchmark on a system's CU grid and page interleaving: each GPU owns
    a private region, a shared region (interleaved pages) receives
    ``shared_frac`` of the accesses, streams walk sequentially with
    ``reuse`` re-touches, and ``rw_share`` of the writes update a hot
    shared slice in place; a fence every 512 rounds."""
    rng = np.random.default_rng(seed)
    NC = system["n_gpus"] * system["cus_per_gpu"]
    CU, G, PB = system["cus_per_gpu"], system["n_gpus"], \
        system["page_blocks"]
    total_blocks = min(int(bench["footprint_mb"] * 1024 * 1024 / 64),
                       1 << 20)
    shared_blocks = max(1024, int(total_blocks * 0.5))
    priv_blocks = max(512, (total_blocks - shared_blocks) // G)
    priv_blocks = (priv_blocks + PB - 1) // PB * PB

    def priv_addr(g: int, b: int) -> int:
        page, off = divmod(b, PB)
        return (page * G + g) * PB + off

    ops = np.zeros((NC, rounds), np.int32)
    addrs = np.zeros((NC, rounds), np.int32)
    shared_base = priv_blocks * G
    gpu_start = rng.integers(0, shared_blocks, G)
    duty = 4 if bench["kind"] == "compute" else 8
    half = priv_blocks // 2
    wf, rw, reuse, shf = (bench["write_frac"], bench["rw_share"],
                          bench["reuse"], bench["shared_frac"])
    for cu in range(NC):
        g = cu // CU
        pos = rng.integers(0, half)
        pos_w = rng.integers(0, half)
        pos_sh = (gpu_start[g] + (cu % CU) * 4) % shared_blocks
        recent = np.zeros(8, np.int64)
        for t in range(rounds):
            if t % 512 == 511:
                ops[cu, t] = FENCE
                continue
            if t % duty == duty - 1:
                ops[cu, t] = COMPUTE
                addrs[cu, t] = bench["compute_per_mem"] * duty
                continue
            write = rng.random() < wf
            r = rng.random()
            if write and rng.random() < rw:
                a = shared_base + int(rng.integers(0, 2 * PB))
            elif write:
                out_slice = max(16, half // CU)
                pos_w = (pos_w + 1) % out_slice
                a = priv_addr(g, half + ((cu % CU) * out_slice + pos_w)
                              % half)
            elif r < reuse:
                a = recent[rng.integers(0, 8)]
            elif r < reuse + shf:
                if r >= reuse + shf * (1 - rw):
                    a = shared_base + int(rng.integers(0, 2 * PB))
                else:
                    pos_sh = (pos_sh + 1) % shared_blocks
                    a = shared_base + pos_sh
                recent[t % 8] = a
            else:
                pos = (pos + 1) % half
                a = priv_addr(g, (pos + cu * 131) % half)
                recent[t % 8] = a
            ops[cu, t] = WRITE if write else READ
            addrs[cu, t] = a
    return ops, addrs


def pack_batch(traces):
    """``[(ops, addrs), ...]`` of equal shape -> ``[B, NC, R]`` arrays."""
    return (np.stack([o for o, _ in traces]),
            np.stack([a for _, a in traces]))
