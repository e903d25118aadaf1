"""Vectorized MGPU memory-hierarchy simulator.

TPU-native re-formulation of the paper's event-driven MGPUSim model: the
protocol advances in *rounds* (one instruction per CU per round) inside a
``lax.scan``; every L1/L2/TSU probe, fill and timestamp update is executed as
a dense array operation batched over all 128+ CUs at once.  Since the
array-native refactor (DESIGN.md §7) the engine holds its hierarchy as
``core.state`` pytrees (``TierState`` for L1/L2, ``TSUState`` for the TSU)
and every transition — probe, victim choice, TSU grant, fused probe+install
— is a call into ``core.state``; this file only contributes *timing* (a
mean-value queueing model: fixed component latencies plus per-round
occupancy delays at L2 banks / HBM stacks / PCIe links) and the per-config
routing/gating policy.  The L1 and L2 probe+install math is served by
``kernels.lease_probe`` (compiled Pallas on TPU/GPU, interpret fallback on
CPU, selected at runtime) via ``state.tier_probe``.

Two drivers (DESIGN.md §5):

- ``simulate(cfg, ops, addrs)`` — one (config, trace) cell; returns the
  per-round read log and final state for litmus-level inspection.
- ``sweep(cfgs, ops, addrs)`` — the batched figure engine: ops/addrs are a
  padded ``[B, NC, R]`` benchmark batch (``traces.pack_batch``), configs are
  grouped by ``sysconfig.static_key`` and stacked into vmappable pytrees,
  and ONE jit produces the whole (config x benchmark) result matrix.

Modeled systems (sysconfig.py): RDMA-WB-NC, RDMA-WB-C-HMG (VI-style home
directory over PCIe), SM-WB-NC, SM-WT-NC, SM-WT-C-HALCONE.

Approximations vs. the event-driven original (documented in DESIGN.md §4):
lockstep instruction issue (per-CU latencies still accrue independently);
same-round same-address writes share one logical tick (ties broken by
physical order, as §3.2); queueing delay is the mean of the round's occupancy
rather than a per-message schedule.

Trace op encoding: 0=nop, 1=read, 2=write, 3=fence (kernel boundary -> cts
jumps to the global maximum), 4=compute (addr field = cycles).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import protocol, state as S
from repro.core.state import INVALID, RES_FIELDS, TSUState, TierState
from repro.core.sysconfig import SystemConfig, stack_configs, static_key
from repro.obs import trace as obs

NOP, READ, WRITE, FENCE, COMPUTE = 0, 1, 2, 3, 4


class SimState(NamedTuple):
    l1: TierState          # per CU               [NC, S1, W1+1]
    l2: TierState          # per (gpu*banks)      [NL2, S2, W2+1]
    l2_dirty: jnp.ndarray  # WB policy bit        [NL2, S2, W2+1]
    tsu: TSUState          # per HBM stack        [NH, ST, TW+1]
    # main memory (authoritative data versions)
    mm_ver: jnp.ndarray    # [A]
    # HMG directory
    dir_sharers: jnp.ndarray  # [A, G] bool (hmg only; [1,1] otherwise)
    # timing / counters
    time: jnp.ndarray      # [NC] f32
    ctr: dict              # scalars f32

    # -- flat-field views kept for litmus/demo inspection of results --
    l1_tag = property(lambda s: s.l1.tag)
    l1_rts = property(lambda s: s.l1.rts)
    l1_wts = property(lambda s: s.l1.wts)
    l1_ver = property(lambda s: s.l1.ver)
    l1_lru = property(lambda s: s.l1.lru)
    l1_cts = property(lambda s: s.l1.cts)
    l2_tag = property(lambda s: s.l2.tag)
    l2_rts = property(lambda s: s.l2.rts)
    l2_wts = property(lambda s: s.l2.wts)
    l2_ver = property(lambda s: s.l2.ver)
    l2_lru = property(lambda s: s.l2.lru)
    l2_cts = property(lambda s: s.l2.cts)
    tsu_tag = property(lambda s: s.tsu.tag)
    tsu_memts = property(lambda s: s.tsu.memts)


COUNTERS = ("l1_to_l2", "l2_to_mm", "l1_hits", "l2_hits", "coh_miss_l1",
            "coh_miss_l2", "wb_evictions", "inval_msgs", "pcie_blocks",
            "reads", "writes",
            # Fig-10 per-link traffic (state.link_bytes): data blocks are
            # BLOCK_BYTES, invalidations CTRL_BYTES; HALCONE's inter-GPU
            # bytes carry no invalidation component by construction.
            "bytes_l1_l2", "bytes_l2_mm", "bytes_inter_gpu")


def init_state(cfg: SystemConfig, n_addr: int) -> SimState:
    NC = cfg.n_cus
    NL2 = cfg.n_gpus * cfg.l2_banks
    G = cfg.n_gpus if cfg.protocol == "hmg" else 1
    A = n_addr if cfg.protocol == "hmg" else 1
    return SimState(
        l1=S.init_tier(NC, cfg.l1_sets, cfg.l1_ways),
        l2=S.init_tier(NL2, cfg.l2_sets, cfg.l2_ways),
        l2_dirty=jnp.zeros((NL2, cfg.l2_sets, cfg.l2_ways + 1), bool),
        tsu=S.init_tsu(cfg.n_hbm, cfg.tsu_sets, cfg.tsu_ways),
        mm_ver=jnp.zeros((n_addr,), jnp.int32),
        dir_sharers=jnp.zeros((A, G), bool),
        time=jnp.zeros((NC,), jnp.float32),
        ctr={k: jnp.zeros((), jnp.float32) for k in COUNTERS},
    )


def _queue_delay(cache_idx, active, n_queues, service):
    """Saturation queueing: a round's n requests to one port drain serially,
    so each waits ~(n-1)*service (calibrated against Fig 8's saturation)."""
    counts = jnp.zeros((n_queues,), jnp.float32).at[
        jnp.where(active, cache_idx, 0)].add(active.astype(jnp.float32))
    mine = counts[cache_idx]
    return jnp.where(active, jnp.maximum(mine - 1.0, 0.0) * service, 0.0)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@functools.lru_cache(maxsize=64)
def _sim_fn(cfg: SystemConfig, n_addr: int, T: int):
    step = _make_round(cfg, n_addr)

    def run(state, ops_t, addrs_t):
        return jax.lax.scan(step, state,
                            (ops_t, addrs_t, jnp.arange(T, dtype=jnp.int32)))

    return jax.jit(run)


def simulate(cfg: SystemConfig, ops, addrs):
    """Host wrapper: buckets shapes (compile reuse), runs the scan."""
    ops = np.asarray(ops, np.int32)
    addrs = np.asarray(addrs, np.int32)
    n_addr = _next_pow2(int(addrs.max()) + 2)
    T0 = ops.shape[1]
    T = _next_pow2(T0)
    if T != T0:                              # pad with NOPs (no effect)
        pad = ((0, 0), (0, T - T0))
        ops = np.pad(ops, pad)
        addrs = np.pad(addrs, pad)
    state = init_state(cfg, n_addr)
    with obs.span("engine.simulate.scan", cat="engine", T=T):
        state, res_log = _sim_fn(cfg, n_addr, T)(state, jnp.asarray(ops).T,
                                                 jnp.asarray(addrs).T)
        obs.fence(res_log, "engine.simulate.device")
    with obs.span("engine.simulate.decode", cat="engine"):
        # scan emits the packed per-round result block [T, 7, NC]
        # (core.state.RES_FIELDS); reshape to per-field [NC, T0] views
        res_np = np.asarray(res_log).transpose(1, 2, 0)[:, :, :T0]
        fields = dict(zip(RES_FIELDS, res_np))
        read_log = np.where(ops[:, :T0] == READ, fields["version"], -1)
    # Runtime: CUs within a GPU hide each other's latency (warp interleaving)
    # -> per-GPU throughput ~ mean CU time; GPUs don't share work -> max.
    per_gpu = state.time.reshape(cfg.n_gpus, cfg.cus_per_gpu).mean(axis=1)
    return {
        "cycles": jnp.max(per_gpu),
        "makespan_max": jnp.max(state.time),
        "per_cu_time": state.time,
        "counters": state.ctr,
        "read_log": read_log,  # [NC, T] version returned (-1 = no read)
        "res_log": fields,     # {RES_FIELDS: [NC, T]} per-op result block
        "state": state,
    }


# --------------------------------------------------------------- sweep
@functools.partial(jax.jit, static_argnames=("n_addr",))
def _sweep_run(groups, ops_bt, addrs_bt, *, n_addr):
    """groups: tuple of stacked SystemConfig pytrees (data leaves [Ci]);
    ops_bt/addrs_bt: [B, T, NC].  Returns a tuple of per-group result
    pytrees with leading [Ci, B] axes — the whole grid in one jit."""
    T = ops_bt.shape[1]

    def one(cfg, ops_t, addrs_t):
        step = _make_round(cfg, n_addr, with_log=False)
        st, _ = jax.lax.scan(step, init_state(cfg, n_addr),
                             (ops_t, addrs_t,
                              jnp.arange(T, dtype=jnp.int32)))
        per_gpu = st.time.reshape(cfg.n_gpus, cfg.cus_per_gpu).mean(axis=1)
        return {"cycles": jnp.max(per_gpu), "makespan_max": jnp.max(st.time),
                "counters": st.ctr}

    over_b = jax.vmap(one, in_axes=(None, 0, 0))      # benchmark axis
    over_cb = jax.vmap(over_b, in_axes=(0, None, None))  # config axis
    return tuple(over_cb(g, ops_bt, addrs_bt) for g in groups)


def sweep(cfgs: Sequence[SystemConfig], ops, addrs):
    """Batched (config x benchmark) sweep — the figure engine.

    ops/addrs: ``[B, NC, R]`` (``traces.pack_batch``); every config must
    have ``n_cus == NC``.  Configs are grouped by structural signature
    (``sysconfig.static_key``); each group is stacked into one pytree and
    double-vmapped (configs x benchmarks) over a shared scan, all groups
    inside ONE jit.  Returns ``{"cycles": [C, B], "makespan_max": [C, B],
    "counters": {k: [C, B]}}`` in the input config order.  Identical math
    to per-cell ``simulate`` (tests/test_sweep.py asserts parity); the
    per-round read log is elided to keep the batch memory-light."""
    cfgs = list(cfgs)
    with obs.span("engine.sweep.pack", cat="engine"):
        ops = np.asarray(ops, np.int32)
        addrs = np.asarray(addrs, np.int32)
        if ops.ndim != 3:
            raise ValueError(f"expected [B, NC, R] batch, got {ops.shape}")
        B, NC, R = ops.shape
        for c in cfgs:
            if c.n_cus != NC:
                raise ValueError(f"config {c.name} has n_cus={c.n_cus}, "
                                 f"traces have NC={NC}")
        n_addr = _next_pow2(int(addrs.max()) + 2)
        T = _next_pow2(R)
        if T != R:                           # pad with NOPs (no effect)
            pad = ((0, 0), (0, 0), (0, T - R))
            ops = np.pad(ops, pad)
            addrs = np.pad(addrs, pad)
        ops_bt = jnp.asarray(ops.transpose(0, 2, 1))     # [B, T, NC]
        addrs_bt = jnp.asarray(addrs.transpose(0, 2, 1))
        # group configs by static structure, first-appearance order
        order: dict = {}
        for i, c in enumerate(cfgs):
            order.setdefault(static_key(c), []).append(i)
        groups = tuple(stack_configs([cfgs[i] for i in idx])
                       for idx in order.values())
    with obs.span("engine.sweep.scan", cat="engine",
                  n_groups=len(groups)):
        outs = _sweep_run(groups, ops_bt, addrs_bt, n_addr=n_addr)
        obs.fence(outs, "engine.sweep.device")
    with obs.span("engine.sweep.decode", cat="engine"):
        # scatter group rows back to the input config order
        flat_idx = [i for idx in order.values() for i in idx]
        perm = np.argsort(flat_idx)
        merged = jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs], 0),
            *outs)
        return jax.tree_util.tree_map(lambda x: x[perm], merged)


def _make_round(cfg: SystemConfig, n_addr: int, with_log: bool = True):
    NC = cfg.n_cus
    G, NB, CU = cfg.n_gpus, cfg.l2_banks, cfg.cus_per_gpu
    NL2 = G * NB
    NH = cfg.n_hbm
    coherent = cfg.protocol == "halcone"
    hmg = cfg.protocol == "hmg"
    rdma = cfg.topology == "rdma"
    wb = cfg.l2_policy == "wb"
    cu_ids = jnp.arange(NC, dtype=jnp.int32)
    gpu_of = cu_ids // CU

    def home_gpu(addr):
        return (addr // cfg.page_blocks) % G

    def hbm_of(addr):
        return (addr // cfg.page_blocks) % NH

    def round_step(st: SimState, xs):
        op, addr, rnd = xs
        is_read = op == READ
        is_write = op == WRITE
        is_fence = op == FENCE
        is_comp = op == COMPUTE
        mem = is_read | is_write
        ctr = dict(st.ctr)

        # ---------------- request routing (addr-only, no probes) ----------
        s1 = addr % cfg.l1_sets
        remote = (home_gpu(addr) != gpu_of) & rdma
        # L2 instance: SM -> own GPU; RDMA-NC -> home GPU's L2;
        # HMG -> local first, then home.
        bank = addr % NB
        own_l2 = gpu_of * NB + bank
        home_l2 = home_gpu(addr) * NB + bank
        if rdma and not hmg:
            l2c = jnp.where(remote, home_l2, own_l2)
        else:
            l2c = own_l2
        s2 = (addr // NB) % cfg.l2_sets
        hb = hbm_of(addr)

        # ---------------- TSU lease math (values; gating applied later) ---
        # The grant (mwts, mrts) a request WOULD get from the TSU.  Whether
        # it reaches the TSU (need_mm) is only known after the L1/L2 probes;
        # state updates are gated below.
        if coherent:
            ts_set = addr % cfg.tsu_sets
            hitT, wayT = S.probe(st.tsu.tag, hb, ts_set, addr)
            vT = S.victim(st.tsu.tag, st.tsu.memts, hb, ts_set)
            wayT = jnp.where(hitT, wayT, vT)
            memts = jnp.where(hitT, st.tsu.memts[hb, ts_set, wayT], 0)
            grant = S.tsu_lease(memts, is_write, cfg.rd_lease, cfg.wr_lease)
            mwts, mrts, new_memts = grant.wts, grant.rts, grant.new_memts
        else:
            # trivial grant: [0, inf) — install math then yields the
            # always-valid lease non-coherent blocks carry
            mwts = jnp.zeros((NC,), jnp.int32)
            mrts = jnp.full((NC,), 2**30, jnp.int32)

        # ---------------- L2 probe + install math (Pallas hot path) -------
        # hit2u is UNGATED by need_l2 (not known yet).  Rows that turn out
        # not to reach L2 discard every derived value below: L2/L1 installs
        # are masked by l2_install/l1_install, both of which imply need_l2.
        (hit2_tag, hit2u, way2, rts2, l2_bwts, l2_brts, l2_ncts) = \
            S.tier_probe(st.l2, l2c, s2, addr, mwts, mrts)

        # HMG second-level probe at the home node for local misses
        if hmg:
            (hitH_tag, _, wayH, _, _, _, _) = \
                S.tier_probe(st.l2, home_l2, s2, addr, mwts, mrts)
            home_hit_u = hitH_tag & ~hit2u & remote
        else:
            wayH = way2
            home_hit_u = jnp.zeros_like(hit2u)

        # ---------------- response lease travelling up to L1 --------------
        # who reaches MM:  WT: all writes; WB: write misses (allocate) + read
        # misses.  HALCONE: writes always; read misses.  (ungated variant)
        if wb:
            need_mm_u = ~hit2u & ~home_hit_u
        else:
            need_mm_u = is_write | (~hit2u & ~home_hit_u)
        wts_from_l2 = jnp.where(hit2u | home_hit_u,
                                jnp.where(hit2u, st.l2.wts[l2c, s2, way2],
                                          st.l2.wts[home_l2, s2, wayH]),
                                mwts)
        rts_from_l2 = jnp.where(hit2u | home_hit_u,
                                jnp.where(hit2u, rts2,
                                          st.l2.rts[home_l2, s2, wayH]),
                                mrts)
        # lease hits keep their timestamps; misses and writes take the fresh
        # install (writes refresh the lease even on a hit)
        l2_new_wts = jnp.where(hit2u & ~is_write,
                               st.l2.wts[l2c, s2, way2], l2_bwts)
        l2_new_rts = jnp.where(hit2u & ~is_write, rts2, l2_brts)
        resp_wts = jnp.where(need_mm_u | is_write, l2_new_wts, wts_from_l2)
        resp_rts = jnp.where(need_mm_u | is_write, l2_new_rts, rts_from_l2)

        # ---------------- L1 probe + install math (Pallas hot path) -------
        (hit1_tag, hit1u, way1, _, l1_new_wts, l1_new_rts, l1_ncts) = \
            S.tier_probe(st.l1, cu_ids, s1, addr, resp_wts, resp_rts)
        l1_lease = protocol.Lease(l1_new_wts, l1_new_rts)
        l1_hit = hit1u & mem
        coh1 = hit1_tag & mem & (~l1_hit)
        need_l2 = (is_read & ~l1_hit) | is_write        # WT L1, writes descend

        # ---------------- gate the L2/MM outcomes -------------------------
        l2_hit = hit2u & need_l2
        coh2 = hit2_tag & need_l2 & (~l2_hit)
        home_hit = home_hit_u & need_l2
        if wb:
            need_mm = need_l2 & ~l2_hit & ~home_hit
        else:
            need_mm = is_write | (need_l2 & ~l2_hit & ~home_hit)

        # ---------------- TSU state updates -------------------------------
        if coherent:
            tsu = S.tsu_commit_scatter(st.tsu, hb, ts_set, wayT, addr,
                                       new_memts, need_mm, hitT)
        else:
            tsu = st.tsu

        # MM data versions: writes increment (scatter-add); then everyone
        # who reads MM sees the post-round version (same-tick semantics).
        wr_mask = is_write
        mm_ver = st.mm_ver.at[jnp.where(wr_mask, addr, n_addr - 1)].add(
            wr_mask.astype(jnp.int32))
        mm_val = mm_ver[addr]

        # ---------------- response values ----------------
        l1_val = st.l1.ver[cu_ids, s1, way1]
        l2_val = st.l2.ver[l2c, s2, way2]
        home_val = st.l2.ver[home_l2, s2, wayH]
        read_val = jnp.where(l1_hit, l1_val,
                             jnp.where(l2_hit, l2_val,
                                       jnp.where(home_hit, home_val, mm_val)))

        # value that lands in caches on a write: the post-write version
        fill_val = jnp.where(is_write, mm_val, read_val)

        # ---------------- install into L2 ----------------
        l2_install = need_l2 & (~l2_hit | is_write)
        v2 = S.victim(st.l2.tag, st.l2.lru, l2c, s2)
        w2i = jnp.where(l2_hit, way2, v2)
        dirty_evict = (st.l2_dirty[l2c, s2, w2i] &
                       (st.l2.tag[l2c, s2, w2i] != INVALID) & ~l2_hit &
                       l2_install) if wb else jnp.zeros_like(l2_install)
        w2s = jnp.where(l2_install, w2i, cfg.l2_ways)       # trash slot
        l2_tag = st.l2.tag.at[l2c, s2, w2s].set(
            jnp.where(l2_install, addr, INVALID))
        l2_ver = st.l2.ver.at[l2c, s2, w2s].set(fill_val)
        l2_rts = st.l2.rts.at[l2c, s2, w2s].set(l2_new_rts)
        l2_wts = st.l2.wts.at[l2c, s2, w2s].set(l2_new_wts)
        l2_lru_new = st.l2.lru.at[l2c, s2,
                                  jnp.where(need_l2, w2i, cfg.l2_ways)].set(rnd)
        l2_dirty = st.l2_dirty
        if wb:
            l2_dirty = l2_dirty.at[l2c, s2, w2s].set(is_write & l2_install)
            l2_dirty = l2_dirty.at[
                l2c, s2, jnp.where(l2_hit & is_write, way2,
                                   cfg.l2_ways)].set(True)
        if coherent:
            # max with 0 is a no-op for non-writers; the kernel's new_cts IS
            # cts_after_write(l2_cts, l2_bwts) for the write's fresh lease
            l2_cts = st.l2.cts.at[l2c].max(jnp.where(is_write, l2_ncts, 0))
        else:
            l2_cts = st.l2.cts

        # HMG: writer invalidates every sharer copy (VI), pays PCIe msgs
        inval_msgs = jnp.zeros((), jnp.float32)
        if hmg:
            shr = st.dir_sharers[addr]                       # [NC, G]
            n_shr = (shr.sum(-1) - shr[cu_ids, gpu_of]) * is_write
            inval_msgs = jnp.sum(n_shr.astype(jnp.float32))
            # membership test instead of an all-pairs compare: mark written
            # addresses in a dense table, gather it at every live tag.
            # (real addrs are < n_addr-1, so the trash row stays False)
            written = jnp.zeros((n_addr,), bool).at[
                jnp.where(is_write, addr, n_addr - 1)].max(is_write)
            safe_tag = jnp.where(l2_tag >= 0, l2_tag, n_addr - 1)
            kill = written[safe_tag]                         # [NL2, S2, W+1]
            # keep the writer's own copy
            own_keep = jnp.zeros_like(kill)
            own_keep = own_keep.at[l2c, s2, w2s].set(is_write)
            l2_tag = jnp.where(kill & ~own_keep, INVALID, l2_tag)
            new_shr = jnp.zeros_like(shr)
            new_shr = new_shr.at[cu_ids, gpu_of].set(is_write | is_read)
            dir_sharers = st.dir_sharers.at[
                jnp.where(is_write, addr, n_addr - 1)].min(
                    jnp.where(is_write[:, None], new_shr, True))
            dir_sharers = dir_sharers.at[
                jnp.where(mem, addr, n_addr - 1), gpu_of].set(True)
        else:
            dir_sharers = st.dir_sharers

        # ---------------- install into L1 ----------------
        l1_install = mem & (~l1_hit | is_write)
        v1 = S.victim(st.l1.tag, st.l1.lru, cu_ids, s1)
        w1i = jnp.where(hit1_tag, way1, v1)
        w1s = jnp.where(l1_install, w1i, cfg.l1_ways)
        l1_tag = st.l1.tag.at[cu_ids, s1, w1s].set(
            jnp.where(l1_install, addr, INVALID))
        l1_ver = st.l1.ver.at[cu_ids, s1, w1s].set(fill_val)
        l1_rts = st.l1.rts.at[cu_ids, s1, w1s].set(l1_lease.rts)
        l1_wts = st.l1.wts.at[cu_ids, s1, w1s].set(l1_lease.wts)
        l1_lru = st.l1.lru.at[cu_ids, s1,
                              jnp.where(mem, w1i, cfg.l1_ways)].set(rnd)
        if coherent:
            # the kernel's new_cts IS cts_after_write(l1_cts, l1_lease.wts)
            l1_cts = jnp.where(is_write, l1_ncts, st.l1.cts)
        else:
            l1_cts = st.l1.cts

        # fences: kernel boundary -> clocks jump to the global max
        if coherent:
            any_fence = jnp.any(is_fence)
            gmax = jnp.maximum(jnp.max(l1_cts), jnp.max(l2_cts))
            l1_cts = jnp.where(is_fence, gmax, l1_cts)
            l2_cts = jnp.where(any_fence, jnp.maximum(l2_cts, gmax), l2_cts)

        # ---------------- timing ----------------
        q_l2 = _queue_delay(l2c, need_l2, NL2, cfg.l2_service)
        mm_users = need_mm | dirty_evict if wb else need_mm
        q_mm = _queue_delay(hb, mm_users, NH, cfg.mm_service)
        pcie_hop = (remote & (need_l2 if not hmg else (need_mm | home_hit))) \
            if rdma else jnp.zeros_like(need_l2)
        q_pcie = _queue_delay(gpu_of, pcie_hop, G, cfg.pcie_service)
        # Reads block the issuing warp for the hierarchy round trip; a CU's
        # other wavefronts overlap ~mlp outstanding misses (latency hiding).
        read_lat = cfg.l1_lat + (
            need_l2 * (cfg.l2_lat + q_l2)
            + need_mm * (cfg.mm_lat + q_mm)
            + pcie_hop * (cfg.pcie_lat + q_pcie)) / cfg.mlp
        # Writes are POSTED: they consume bandwidth (queue terms above count
        # them) but don't stall the warp — except WB write-allocate fetches
        # and the dirty-eviction serialization the paper describes (§5.1).
        write_lat = cfg.l1_lat + q_l2
        if wb:
            write_lat = write_lat + (need_mm * (cfg.mm_lat + q_mm)
                + pcie_hop * (cfg.pcie_lat + q_pcie)) / cfg.mlp
        lat = jnp.where(is_read, read_lat,
                        jnp.where(is_write, write_lat, 0.0))
        if wb:
            lat = lat + dirty_evict * (cfg.mm_lat + q_mm) / cfg.mlp
        lat = lat + is_comp * addr.astype(jnp.float32)
        if hmg:
            lat = lat + is_write * (st.dir_sharers[addr].sum(-1)
                                    > 1) * cfg.pcie_lat
        time = st.time + jnp.where(mem | is_comp, lat, 0.0)

        # ---------------- counters ----------------
        f = lambda x: jnp.sum(x.astype(jnp.float32))
        ctr["reads"] += f(is_read)
        ctr["writes"] += f(is_write)
        ctr["l1_hits"] += f(l1_hit & is_read)
        ctr["l2_hits"] += f(l2_hit & need_l2)
        ctr["l1_to_l2"] += f(need_l2)
        ctr["l2_to_mm"] += f(need_mm) + (f(dirty_evict) if wb else 0.0)
        ctr["coh_miss_l1"] += f(coh1 & is_read) if coherent else 0.0
        ctr["coh_miss_l2"] += f(coh2 & is_read) if coherent else 0.0
        ctr["wb_evictions"] += f(dirty_evict) if wb else 0.0
        ctr["inval_msgs"] += inval_msgs if hmg else 0.0
        ctr["pcie_blocks"] += f(pcie_hop) if rdma else 0.0
        b12, b2m, big = S.link_bytes(
            f(need_l2), f(need_mm) + (f(dirty_evict) if wb else 0.0),
            f(pcie_hop) if rdma else 0.0, inval_msgs if hmg else 0.0)
        ctr["bytes_l1_l2"] += b12
        ctr["bytes_l2_mm"] += b2m
        ctr["bytes_inter_gpu"] += big

        new_st = SimState(
            l1=TierState(tag=l1_tag, wts=l1_wts, rts=l1_rts, ver=l1_ver,
                         lru=l1_lru, cts=l1_cts),
            l2=TierState(tag=l2_tag, wts=l2_wts, rts=l2_rts, ver=l2_ver,
                         lru=l2_lru_new, cts=l2_cts),
            l2_dirty=l2_dirty, tsu=tsu, mm_ver=mm_ver,
            dir_sharers=dir_sharers, time=time, ctr=ctr)
        if not with_log:
            return new_st, None
        # packed per-op result block, same [len(RES_FIELDS), lanes] layout
        # the fabric miss pass emits (core.state.RES_FIELDS): one int32
        # stack per round instead of a read-only log, so litmus/telemetry
        # callers see WHERE a request was served (level), which lease it
        # installed (wts/rts) and whether it reached main memory (mm_used).
        lvl = jnp.where(l1_hit, 0,
                        jnp.where(l2_hit, 1,
                                  jnp.where(home_hit, 2, 3)))
        i32 = lambda x: x.astype(jnp.int32)
        res = jnp.stack([
            i32(mem),                                        # found
            jnp.where(is_read, read_val,                     # version
                      jnp.where(is_write, mm_val, -1)),
            jnp.full((NC,), -1, jnp.int32),                  # gseq (n/a)
            jnp.where(is_read, lvl, -1),                     # level
            jnp.where(mem, l1_lease.wts, -1),                # wts
            jnp.where(mem, l1_lease.rts, -1),                # rts
            i32(need_mm),                                    # mm_used
        ])
        return new_st, res

    return round_step
