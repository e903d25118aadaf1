"""Plain reference of the figure engine's counters: the paper's five
multi-GPU memory systems (HALCONE, arXiv 2007.04292, section 4.1) run
round by round over a ``[B, NC, R]`` batch of traces, in numpy, importing
nothing of the program.

The model, per round (every CU issues one op; ops 0 nop, 1 read,
2 write, 3 fence, 4 compute):

  * Routing: L1 set ``addr % l1_sets``; L2 bank ``addr % l2_banks``, set
    ``(addr // l2_banks) % l2_sets``; a page of ``page_blocks`` blocks has
    home GPU ``page % n_gpus`` and HBM stack ``page % n_hbm``.  Under RDMA
    without coherence a remote block is served by its home GPU's L2.
  * Tiers hold (tag, wts, rts, version, lru); a probe hits on the first
    way holding the tag whose ``rts >= cts`` of its cache (leases apply to
    every system: non-coherent systems are granted [0, 2**30)); victims
    are the first invalid way, else the least recently used.
  * HALCONE (Algorithm 3): a request reaching memory is granted
    [memts, memts + rd_lease] for a read, [memts + 1, memts + wr_lease]
    for a write, memts' = the grant's rts, restarting at [0, lease] past
    65535; tiers install max(cts, wts), max(wts + 1, rts); writes advance
    the writer's clocks; a fence raises every clock to the highest.  The
    TSU is set-associative per HBM stack; same-round commits to one entry
    keep the largest tag and clock.
  * Write-through L2s send every write to memory; write-back L2s send
    misses and write back dirty victims.  HMG keeps a home directory of
    sharing GPUs; a write invalidates the other GPUs' L2 copies, one
    message per sharing GPU.
  * Within a round, requests see the state as it was before the round;
    where several requests write one slot, the last CU's write stays.

Counters (``COUNTERS``) are summed over the rounds: the same names and
meaning as the engine reports, link bytes at 64 per block and 8 per
invalidation.
"""
from __future__ import annotations

import numpy as np

NOP, READ, WRITE, FENCE, COMPUTE = 0, 1, 2, 3, 4
INVALID = -1
TS_MAX = (1 << 16) - 1
BLOCK, CTRL = 64, 8
COUNTERS = ("l1_to_l2", "l2_to_mm", "l1_hits", "l2_hits", "coh_miss_l1",
            "coh_miss_l2", "wb_evictions", "inval_msgs", "pcie_blocks",
            "reads", "writes", "bytes_l1_l2", "bytes_l2_mm",
            "bytes_inter_gpu")
i32 = np.int32


class _Tier:
    def __init__(self, B, n, sets, ways):
        shp = (B, n, sets, ways + 1)          # last way: a discard slot
        self.tag = np.full(shp, INVALID, i32)
        self.wts = np.zeros(shp, i32)
        self.rts = np.zeros(shp, i32)
        self.ver = np.zeros(shp, i32)
        self.lru = np.zeros(shp, i32)
        self.cts = np.zeros((B, n), i32)


def _rows(arr, b, idx, s):
    return arr[b, idx, s][..., :-1]


def _probe(tier, b, idx, s, addr):
    """(tag hit, first matching way or 0)."""
    eq = _rows(tier.tag, b, idx, s) == addr[..., None]
    return eq.any(-1), np.argmax(eq, -1)


def _victim(tag, score, b, idx, s):
    rows = _rows(score, b, idx, s)
    rows = np.where(_rows(tag, b, idx, s) == INVALID, -(2 ** 30), rows)
    return np.argmin(rows, -1)


def _lease_probe(tier, b, idx, s, addr, mwts, mrts):
    """Probe plus install arithmetic: (tag hit, hit, way, rts at the way,
    installed wts, installed rts, clock after a write)."""
    hit_t, way = _probe(tier, b, idx, s, addr)
    cts = tier.cts[b, idx]
    row_rts = np.where(hit_t, np.take_along_axis(
        _rows(tier.rts, b, idx, s), way[..., None], -1)[..., 0], 0)
    hit = hit_t & (cts <= row_rts)
    bwts = np.maximum(cts, mwts)
    brts = np.maximum(bwts + 1, mrts)
    return hit_t, hit, way, row_rts, bwts, brts, np.maximum(cts, bwts)


def _set(arr, index, vals):
    arr[index] = vals                     # repeated slots: the last stays


def simulate(system: dict, ops: np.ndarray, addrs: np.ndarray,
             fence_raises_clocks: bool = True) -> dict:
    """Counters ``{name: int64 [B]}`` of one system over a ``[B, NC, R]``
    trace batch.  ``fence_raises_clocks=False`` is the control: HALCONE
    with one stated guarantee broken, its fences leaving every clock where
    it was."""
    c = system
    ops = np.asarray(ops, i32).transpose(0, 2, 1)       # [B, R, NC]
    addrs = np.asarray(addrs, i32).transpose(0, 2, 1)
    B, R, NC = ops.shape
    G, NB, CU = c["n_gpus"], c["l2_banks"], c["cus_per_gpu"]
    NL2, NH = G * NB, c["n_hbm"]
    S1, W1, S2, W2 = c["l1_sets"], c["l1_ways"], c["l2_sets"], c["l2_ways"]
    coherent, hmg = c["protocol"] == "halcone", c["protocol"] == "hmg"
    rdma, wb = c["topology"] == "rdma", c["l2_policy"] == "wb"
    n_addr = 1
    while n_addr < int(addrs.max()) + 2:
        n_addr <<= 1
    l1 = _Tier(B, NC, S1, W1)
    l2 = _Tier(B, NL2, S2, W2)
    l2_dirty = np.zeros((B, NL2, S2, W2 + 1), bool)
    tsu_tag = np.full((B, NH, c["tsu_sets"], c["tsu_ways"] + 1), INVALID,
                      i32)
    tsu_mem = np.zeros_like(tsu_tag)
    mm_ver = np.zeros((B, n_addr), i32)
    dirs = np.zeros((B, n_addr, G), bool) if hmg else None
    ctr = {k: np.zeros(B, np.int64) for k in COUNTERS}
    b = np.arange(B)[:, None]
    cu = np.broadcast_to(np.arange(NC, dtype=i32), (B, NC))
    gpu = cu // CU

    for t in range(R):
        op, addr, rnd = ops[:, t], addrs[:, t], i32(t)
        is_r, is_w, is_f = op == READ, op == WRITE, op == FENCE
        mem = is_r | is_w
        s1 = addr % S1
        page = addr // c["page_blocks"]
        home = page % G
        remote = (home != gpu) & rdma
        bank = addr % NB
        own_l2, home_l2 = gpu * NB + bank, home * NB + bank
        l2c = np.where(remote, home_l2, own_l2) if (rdma and not hmg) \
            else own_l2
        s2 = (addr // NB) % S2
        hb = page % NH

        if coherent:
            ts = addr % c["tsu_sets"]
            eq = tsu_tag[b, hb, ts][..., :-1] == addr[..., None]
            hit_t, way_t = eq.any(-1), np.argmax(eq, -1)
            v_t = _victim(tsu_tag, tsu_mem, b, hb, ts)
            way_t = np.where(hit_t, way_t, v_t)
            memts = np.where(hit_t, tsu_mem[b, hb, ts, way_t], 0)
            lease = np.where(is_w, c["wr_lease"], c["rd_lease"])
            mwts = np.where(is_w, memts + 1, memts)
            mrts = memts + lease
            ovf = mrts > TS_MAX
            mwts = np.where(ovf, 0, mwts)
            mrts = np.where(ovf, lease, mrts)
            new_memts = mrts
        else:
            mwts = np.zeros((B, NC), i32)
            mrts = np.full((B, NC), 2 ** 30, i32)

        hit2_t, hit2u, way2, rts2, l2_bwts, l2_brts, l2_ncts = \
            _lease_probe(l2, b, l2c, s2, addr, mwts, mrts)
        if hmg:
            hith_t, _, way_h, _, _, _, _ = \
                _lease_probe(l2, b, home_l2, s2, addr, mwts, mrts)
            home_hit_u = hith_t & ~hit2u & remote
        else:
            way_h = way2
            home_hit_u = np.zeros_like(hit2u)
        need_mm_u = (~hit2u & ~home_hit_u) if wb else \
            (is_w | (~hit2u & ~home_hit_u))
        wts_l2 = np.where(hit2u | home_hit_u,
                          np.where(hit2u, l2.wts[b, l2c, s2, way2],
                                   l2.wts[b, home_l2, s2, way_h]), mwts)
        rts_l2 = np.where(hit2u | home_hit_u,
                          np.where(hit2u, rts2,
                                   l2.rts[b, home_l2, s2, way_h]), mrts)
        l2_new_wts = np.where(hit2u & ~is_w, l2.wts[b, l2c, s2, way2],
                              l2_bwts)
        l2_new_rts = np.where(hit2u & ~is_w, rts2, l2_brts)
        resp_wts = np.where(need_mm_u | is_w, l2_new_wts, wts_l2)
        resp_rts = np.where(need_mm_u | is_w, l2_new_rts, rts_l2)

        hit1_t, hit1u, way1, _, l1_wts_new, l1_rts_new, l1_ncts = \
            _lease_probe(l1, b, cu, s1, addr, resp_wts, resp_rts)
        l1_hit = hit1u & mem
        coh1 = hit1_t & mem & ~l1_hit
        need_l2 = (is_r & ~l1_hit) | is_w
        l2_hit = hit2u & need_l2
        coh2 = hit2_t & need_l2 & ~l2_hit
        home_hit = home_hit_u & need_l2
        need_mm = (need_l2 & ~l2_hit & ~home_hit) if wb else \
            (is_w | (need_l2 & ~l2_hit & ~home_hit))

        if coherent:
            tw = np.where(need_mm, way_t, c["tsu_ways"])
            np.maximum.at(tsu_tag, (b, hb, ts, tw),
                          np.where(need_mm, addr, INVALID))
            cleared = np.where(need_mm & ~hit_t, 0, tsu_mem[b, hb, ts, tw])
            _set(tsu_mem, (b, hb, ts, tw),
                 np.where(need_mm, np.maximum(cleared, 0), cleared))
            np.maximum.at(tsu_mem, (b, hb, ts, tw),
                          np.where(need_mm, new_memts, 0))

        np.add.at(mm_ver, (b, np.where(is_w, addr, n_addr - 1)),
                  is_w.astype(i32))
        mm_val = mm_ver[b, addr]
        read_val = np.where(
            l1_hit, l1.ver[b, cu, s1, way1],
            np.where(l2_hit, l2.ver[b, l2c, s2, way2],
                     np.where(home_hit, l2.ver[b, home_l2, s2, way_h],
                              mm_val)))
        fill_val = np.where(is_w, mm_val, read_val)

        l2_install = need_l2 & (~l2_hit | is_w)
        v2 = _victim(l2.tag, l2.lru, b, l2c, s2)
        w2i = np.where(l2_hit, way2, v2)
        if wb:
            dirty_evict = (l2_dirty[b, l2c, s2, w2i]
                           & (l2.tag[b, l2c, s2, w2i] != INVALID)
                           & ~l2_hit & l2_install)
        else:
            dirty_evict = np.zeros_like(l2_install)
        w2s = np.where(l2_install, w2i, W2)
        _set(l2.tag, (b, l2c, s2, w2s),
             np.where(l2_install, addr, INVALID))
        _set(l2.ver, (b, l2c, s2, w2s), fill_val)
        _set(l2.rts, (b, l2c, s2, w2s), l2_new_rts)
        _set(l2.wts, (b, l2c, s2, w2s), l2_new_wts)
        _set(l2.lru, (b, l2c, s2, np.where(need_l2, w2i, W2)), rnd)
        if wb:
            _set(l2_dirty, (b, l2c, s2, w2s), is_w & l2_install)
            _set(l2_dirty, (b, l2c, s2, np.where(l2_hit & is_w, way2, W2)),
                 True)
        l2_cts = l2.cts.copy()
        if coherent:
            np.maximum.at(l2_cts, (b, l2c), np.where(is_w, l2_ncts, 0))

        inval = np.zeros(B, np.int64)
        if hmg:
            shr = dirs[b, addr]                              # [B, NC, G]
            n_shr = (shr.sum(-1) - shr[b, cu, gpu]) * is_w
            inval = n_shr.sum(-1).astype(np.int64)
            # every L2 copy of a written block goes, but the slots the
            # round's writers installed into; a block lives only in its
            # bank's set of each GPU's L2
            keep = np.zeros(l2.tag.shape, bool)
            _set(keep, (b, l2c, s2, w2s), is_w)
            bb = np.broadcast_to(b[..., None], (B, NC, G))
            inst = np.arange(G, dtype=i32) * NB + bank[..., None]
            ss = np.broadcast_to(s2[..., None], (B, NC, G))
            kill = (l2.tag[bb, inst, ss] == addr[..., None, None]) \
                & ~keep[bb, inst, ss] & is_w[..., None, None]
            kb, kc, kg, kw = np.nonzero(kill)
            l2.tag[kb, inst[kb, kc, kg], ss[kb, kc, kg], kw] = INVALID
            new_shr = np.zeros_like(shr)
            _set(new_shr, (b, cu, gpu), is_w | is_r)
            np.minimum.at(dirs, (b, np.where(is_w, addr, n_addr - 1)),
                          np.where(is_w[..., None], new_shr, True))
            _set(dirs, (b, np.where(mem, addr, n_addr - 1), gpu), True)

        l1_install = mem & (~l1_hit | is_w)
        v1 = _victim(l1.tag, l1.lru, b, cu, s1)
        w1i = np.where(hit1_t, way1, v1)
        w1s = np.where(l1_install, w1i, W1)
        _set(l1.tag, (b, cu, s1, w1s), np.where(l1_install, addr, INVALID))
        _set(l1.ver, (b, cu, s1, w1s), fill_val)
        _set(l1.rts, (b, cu, s1, w1s), l1_rts_new)
        _set(l1.wts, (b, cu, s1, w1s), l1_wts_new)
        _set(l1.lru, (b, cu, s1, np.where(mem, w1i, W1)), rnd)
        l1_cts = np.where(is_w, l1_ncts, l1.cts) if coherent else l1.cts
        if coherent and fence_raises_clocks:
            gmax = np.maximum(l1_cts.max(-1), l2_cts.max(-1))[:, None]
            l1_cts = np.where(is_f, gmax, l1_cts)
            l2_cts = np.where(is_f.any(-1)[:, None],
                              np.maximum(l2_cts, gmax), l2_cts)
        l1.cts, l2.cts = l1_cts, l2_cts

        pcie = (remote & (need_l2 if not hmg else (need_mm | home_hit))) \
            if rdma else np.zeros_like(need_l2)
        n = lambda x: x.sum(-1).astype(np.int64)
        to_mm = n(need_mm) + (n(dirty_evict) if wb else 0)
        ctr["reads"] += n(is_r)
        ctr["writes"] += n(is_w)
        ctr["l1_hits"] += n(l1_hit & is_r)
        ctr["l2_hits"] += n(l2_hit & need_l2)
        ctr["l1_to_l2"] += n(need_l2)
        ctr["l2_to_mm"] += to_mm
        if coherent:
            ctr["coh_miss_l1"] += n(coh1 & is_r)
            ctr["coh_miss_l2"] += n(coh2 & is_r)
        if wb:
            ctr["wb_evictions"] += n(dirty_evict)
        ctr["inval_msgs"] += inval
        ctr["pcie_blocks"] += n(pcie)
        ctr["bytes_l1_l2"] += n(need_l2) * BLOCK
        ctr["bytes_l2_mm"] += to_mm * BLOCK
        ctr["bytes_inter_gpu"] += n(pcie) * BLOCK + inval * CTRL
    return ctr
