"""Array-native coherence fabric: the whole TSU service as device arrays.

This is the production implementation of the ``FabricBackend`` contract
(backend.py).  All coherence state lives in ``core.state`` pytrees:

  * sharded TSU+MM   — a ``[n_shards, capacity]`` table (``TSUState`` with
    one fully-associative set per shard) plus version / allocation-order /
    write-sequence side arrays,
  * replica tier     — ``TierState`` ``[n_replicas, sets, ways+1]``,
  * node-shared tier — ``TierState`` ``[n_nodes, sets, ways+1]``,
  * write queue      — a bounded ring per node, drained in-scan,

and a batch of ops is applied as ONE jitted ``lax.scan`` (``apply``): each
step dispatches on the op kind and runs the same transition sequence the
host objects execute per key — probe, self-invalidate on expiry, descend,
TSU grant (16-bit overflow reinit included), install back up — with every
lease decision served by ``core.state`` (→ ``core.protocol`` + the Pallas
lease-probe kernel).  No timestamp rule is implemented here: this file is
routing, gating and bookkeeping over the shared transition layer.

Values (the actual cached payloads — KV blocks, parameter blobs) stay on
the host: every MM write is stamped with a globally unique write sequence
number (``gseq``) carried alongside each cached line, and the wrapper maps
``gseq -> value``.  The arrays decide *everything* (hits, grants, versions,
evictions); the host only moves payloads per the returned plan.

Bit-identical to ``HostFabric`` on any op trace — grants, hit levels,
versions, and the full ``FabricStats`` block (tests/test_fabric_parity.py,
DESIGN.md §7).
"""
from __future__ import annotations

import collections
import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.coherence.fabric import pipeline as P_
from repro.coherence.fabric.backend import (GRANT_LOG_LEN, FabricBackend,
                                            Op, ReadBatchHandle, _bounded)
from repro.coherence.fabric.stats import GI as _GI
from repro.coherence.fabric.stats import G_KEYS as _G_KEYS
from repro.coherence.fabric.stats import RI as _RI
from repro.coherence.fabric.stats import R_KEYS as _R_KEYS
from repro.coherence.fabric.tsu import FabricConfig, stable_hash
from repro.core import protocol
from repro.core import state as S
from repro.core.state import TSUState, TierState
from repro.obs import trace as obs
from repro.sharding import named_sharding

_NOP, _READ, _WRITE, _FENCE, _MM_WRITE, _PUBLISH, _MM_READ = range(7)
_PRUNE_EVERY = 4096          # payload-map GC cadence, in completed writes
_KIND = {"read": _READ, "write": _WRITE, "fence": _FENCE,
         "mm_write": _MM_WRITE, "publish": _PUBLISH, "mm_read": _MM_READ}

# pipelines: "batched" = one packed grant collective per batch + the
# vectorized miss pass; "scan" = the PR-4 per-op collective schedule,
# kept for ordering-sensitive debugging (DESIGN.md §9)
PIPELINES = ("batched", "scan")
# read_batch falls back to the op-scan when the miss subset needs more
# conflict-free rounds than max(_MIN_ROUND_BUDGET, m // 4): one pass round
# costs a few scan steps of dispatch, so the pipeline stops paying off
# when conflicts (duplicate keys / set collisions) shred the subset into
# near-sequential rounds.  A deduplicated serving batch is 1-2 rounds.
_MIN_ROUND_BUDGET = 6


class _AF(NamedTuple):
    """The device-resident fabric state."""

    rp: TierState            # replica tier [R, S1, W1+1]
    rp_gseq: jnp.ndarray     # write-sequence id per line (payload handle)
    rp_tick: jnp.ndarray     # [R] LRU tick (host _SetAssoc._tick semantics)
    sh: TierState            # shared tier [Nn, S2, W2+1]
    sh_gseq: jnp.ndarray
    sh_tick: jnp.ndarray     # [Nn]
    tsu: TSUState            # [Ks, 1, cap+1]
    tsu_ver: jnp.ndarray     # per-entry version (resets on realloc)
    tsu_gseq: jnp.ndarray
    tsu_seq: jnp.ndarray     # allocation order (victim tie-break)
    tsu_nseq: jnp.ndarray    # [Ks] next allocation seq
    gseq_next: jnp.ndarray   # global write-sequence counter
    wq: Dict[str, jnp.ndarray]   # ring fields [Nn, Q]
    wq_head: jnp.ndarray     # [Nn]
    wq_len: jnp.ndarray      # [Nn]
    g: jnp.ndarray           # global counters [len(_G_KEYS)]
    r: jnp.ndarray           # per-replica counters [R, len(_R_KEYS)]


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _af_pspecs() -> _AF:
    """The fabric state's mesh layout as a ``PartitionSpec`` prefix tree:
    the TSU table and its per-shard sequencers (version / gseq / alloc-seq
    side arrays, next-seq counters) live along the ``fabric`` axis — shard
    rows ``[d*KS/D, (d+1)*KS/D)`` on device ``d`` — while the client tiers,
    write-queue rings and counters are replicated (every device derives
    the identical update from replicated op inputs + broadcast grants)."""
    F, R = P("fabric"), P()
    return _AF(rp=R, rp_gseq=R, rp_tick=R, sh=R, sh_gseq=R, sh_tick=R,
               tsu=F, tsu_ver=F, tsu_gseq=F, tsu_seq=F, tsu_nseq=F,
               gseq_next=R, wq=R, wq_head=R, wq_len=R, g=R, r=R)


@functools.lru_cache(maxsize=32)
def _build_run(S1s, W1, S2s, W2, KS, CAP, NN, NR, Q, MAXIF, LD, MESH=None,
               PIPE="batched"):
    """The jitted op-scan for one static geometry.  Cached so every
    ArrayFabric instance with the same shape shares one compilation.

    With ``MESH`` (a 1-axis ``fabric`` mesh) the scan becomes a
    ``jax.shard_map`` body: the TSU table and its per-shard
    sequencers are laid out along the mesh axis (each device owns
    ``KS / D`` contiguous shards — the paper's one-TSU-per-HBM-stack
    placement).  Client tiers, write-queue rings and counters stay
    replicated: they are updated by identical arithmetic on every device
    (all op inputs and exchanged grants are replicated), so the sharded
    scan is bit-identical to the single-device one.  What travels over
    the fabric axis depends on ``PIPE`` (DESIGN.md §9):

      * ``"scan"``   — the PR-4 schedule: every op's TSU transition
        executes only on its key's owning device and the packed grant
        (wts/rts/version + counter flags) hops back as ONE ``all_gather``
        per scan step — O(ops) collectives per batch.  The rare-op
        ``lax.cond`` gates are replaced by masked execution so each
        device runs the same symmetric collective sequence.  Kept for
        ordering-sensitive debugging.
      * ``"batched"`` — the batched grant pipeline never builds a meshed
        op-scan at all: each device's owned TSU rows (tag/memts/ver/gseq/
        seq/nseq packed into ONE contiguous buffer, ``state.pack_tsu``)
        are exchanged ONCE per batch (``state.owner_gather``, the
        dedicated ``_build_tsu_gather`` program), and the collective-free
        MESH=None programs — this op-scan and the miss/write/fence
        passes — run on the lead device against the assembled table
        (``ArrayFabric._xin``/``_xout``, DESIGN.md §12a).  O(1)
        collectives per batch, one compilation shared with the
        single-device fabric.
    """
    i32 = jnp.int32
    one = jnp.ones((), i32)
    zero = jnp.zeros((), i32)
    NG, NRK = len(_G_KEYS), len(_R_KEYS)
    b2i = lambda b: b.astype(i32)

    sharded = MESH is not None and PIPE == "scan"   # per-op collectives?
    D = int(MESH.devices.size) if MESH is not None else 1
    SPD = KS // D                    # shards per device (divisibility checked
                                     # by the caller)
    if sharded:
        def shard_ctx(shard):
            """Route a (global) home-shard id: the device-local row, an
            am-I-the-owner mask, and the owning device's axis index."""
            me = jax.lax.axis_index("fabric").astype(i32)
            owner = shard // SPD
            lsh = jnp.clip(shard - me * SPD, 0, SPD - 1)
            return lsh, owner == me, owner

        def bcast(owner, *vals):
            """The cross-shard hop: the owner's scalars travel over the
            fabric axis (all_gather), everyone selects the owner's row."""
            rows = jax.lax.all_gather(jnp.stack(vals), "fabric")   # [D, n]
            row = rows[owner]
            return tuple(row[i] for i in range(len(vals)))
    else:
        def shard_ctx(shard):
            return shard, jnp.ones((), bool), zero

        def bcast(owner, *vals):
            return vals

    def gv(**kw):
        """One [NG] increment vector — a single add per counter block."""
        out = jnp.zeros((NG,), i32)
        return out.at[jnp.array([_GI[k] for k in kw], i32)].add(
            jnp.stack([b2i(v) if v.dtype == bool else v
                       for v in kw.values()]))

    def rv(**kw):
        out = jnp.zeros((NRK,), i32)
        return out.at[jnp.array([_RI[k] for k in kw], i32)].add(
            jnp.stack([b2i(v) if v.dtype == bool else v
                       for v in kw.values()]))

    def probe1(tier, idx, st, key, mwts, mrts):
        out = S.tier_probe(tier, idx[None], st[None], key[None],
                           mwts[None], mrts[None])
        return tuple(o[0] for o in out)

    def touch(tier, tick, idx, st, key, active):
        """Host probe semantics: on a tag match, bump the store tick and
        refresh the line's LRU (even if the lease is dead)."""
        th, hit, way, _, _, _, _ = probe1(tier, idx, st, key, zero, zero)
        th, hit = th & active, hit & active
        tick2 = tick.at[idx].add(b2i(th))
        w = jnp.where(th, way, tier.n_ways)
        lru2 = tier.lru.at[idx, st, w].set(
            jnp.where(th, tick2[idx], tier.lru[idx, st, w]))
        return tier._replace(lru=lru2), tick2, th, hit, way

    def drop(tier, idx, st, way, cond):
        w = jnp.where(cond, way, tier.n_ways)
        return tier._replace(tag=tier.tag.at[idx, st, w].set(
            jnp.where(cond, S.INVALID, tier.tag[idx, st, w])))

    def install_at(tier, gseq_a, tick, idx, st, key, wts, rts, ver, gs,
                   th, way, active):
        """Host install semantics with the same-key probe precomputed:
        tick++, in-place on ``(th, way)``, else the victim way (invalid
        first, then LRU); reports displacement of a live different-key
        line (a capacity eviction)."""
        vic = S.victim(tier.tag, tier.lru, idx[None], st[None])[0]
        w0 = jnp.where(th, way, vic)
        evicted = active & ~th & (tier.tag[idx, st, w0] != S.INVALID)
        tick2 = tick.at[idx].add(b2i(active))
        w = jnp.where(active, w0, tier.n_ways)

        def pt(a, v):
            return a.at[idx, st, w].set(jnp.where(active, v, a[idx, st, w]))

        tier2 = TierState(tag=pt(tier.tag, key), wts=pt(tier.wts, wts),
                          rts=pt(tier.rts, rts), ver=pt(tier.ver, ver),
                          lru=pt(tier.lru, tick2[idx]), cts=tier.cts)
        return tier2, pt(gseq_a, gs), tick2, evicted

    F = jnp.zeros((), bool)

    def fill(tier, gseq_a, tick, idx, st, key, wts, rts, ver, gs, active):
        """A fill after a miss: the key cannot be present (an expired line
        was already dropped), so the install always takes the victim way."""
        return install_at(tier, gseq_a, tick, idx, st, key, wts, rts, ver,
                          gs, F, zero, active)

    def tsu_probe(af, shard, key):
        th, way = S.probe(af.tsu.tag, shard[None], zero[None], key[None])
        return th[0], way[0]

    def mm_write1(af, key, shard, wl, rd, wr, active):
        """TSUShard.mm_write: allocate (evicting the min-(memts, alloc-seq)
        entry when the shard is full), grant via Algorithm 3 + overflow
        reinit, bump the version.  Sharded: the transition executes on the
        owning device only; the grant travels back via ``bcast``."""
        lsh, mine, owner = shard_ctx(shard)
        local = active & mine
        th, way = tsu_probe(af, lsh, key)
        vic = S.victim_lex(af.tsu.tag, af.tsu.memts, af.tsu_seq,
                           lsh[None], zero[None])[0]
        full = (af.tsu.tag[lsh, 0][:CAP] != S.INVALID).all()
        evict = local & ~th & full
        w0 = jnp.where(th, way, vic)
        memts = jnp.where(th, af.tsu.memts[lsh, 0, w0], 0)
        wl_eff = jnp.where(wl >= 0, wl, wr)
        gr = S.tsu_lease(memts[None], jnp.ones((1,), bool), rd, wl_eff[None])
        mwts, mrts, nmem, ovf = (gr.wts[0], gr.rts[0], gr.new_memts[0],
                                 gr.overflow[0])
        ver = jnp.where(th, af.tsu_ver[lsh, 0, w0] + 1, 1)
        seqv = jnp.where(th, af.tsu_seq[lsh, 0, w0], af.tsu_nseq[lsh])
        gs = af.gseq_next
        tsu2 = S.tsu_commit_exact(af.tsu, lsh[None], zero[None], w0[None],
                                  key[None], nmem[None], local[None])
        w = jnp.where(local, w0, CAP)

        def pt(a, v):
            return a.at[lsh, 0, w].set(jnp.where(local, v, a[lsh, 0, w]))

        # the grant + counter flags hop from the owning shard's device
        mwts_b, mrts_b, ver_b, evict_i, ovf_i = bcast(
            owner, mwts, mrts, ver, b2i(evict), b2i(active & ovf))
        af = af._replace(
            tsu=tsu2, tsu_ver=pt(af.tsu_ver, ver),
            tsu_gseq=pt(af.tsu_gseq, gs), tsu_seq=pt(af.tsu_seq, seqv),
            tsu_nseq=af.tsu_nseq.at[lsh].add(b2i(local & ~th)),
            gseq_next=af.gseq_next + b2i(active),
            g=af.g + gv(tsu_evictions=evict_i, overflow_reinits=ovf_i))
        return af, mwts_b, mrts_b, ver_b, gs

    def mm_read1(af, key, shard, rd, wr, active):
        """TSUShard.mm_read: grant only if the entry exists (sharded: on the
        owning device; found/grant/version hop back via ``bcast``)."""
        lsh, mine, owner = shard_ctx(shard)
        th, way = tsu_probe(af, lsh, key)
        local_found = active & mine & th
        memts = jnp.where(th, af.tsu.memts[lsh, 0, way], 0)
        gr = S.tsu_lease(memts[None], jnp.zeros((1,), bool), rd, wr)
        mwts, mrts, nmem, ovf = (gr.wts[0], gr.rts[0], gr.new_memts[0],
                                 gr.overflow[0])
        tsu2 = S.tsu_commit_exact(af.tsu, lsh[None], zero[None],
                                  way[None], key[None], nmem[None],
                                  local_found[None])
        ver = af.tsu_ver[lsh, 0, way]
        gs = af.tsu_gseq[lsh, 0, way]
        th_i, mwts, mrts, ver, gs, ovf_i = bcast(
            owner, b2i(th), mwts, mrts, ver, gs, b2i(ovf))
        found = active & (th_i > 0)
        af = af._replace(tsu=tsu2,
                         g=af.g + gv(overflow_reinits=b2i(found) * ovf_i))
        return af, found, mwts, mrts, jnp.where(found, ver, -1), \
            jnp.where(found, gs, -1)

    def drain1(af, node, rd, wr, active):
        """WriteQueue._drain_one: pop the oldest posted write, write through
        to the TSU, adopt the grant into the node tier, then install the
        ADOPTED lease into the submitting replica (the engine's L2-then-L1
        response chain)."""
        h = af.wq_head[node]
        key = af.wq["key"][node, h]
        rep = af.wq["rep"][node, h]
        wl = af.wq["wl"][node, h]
        shard = af.wq["shard"][node, h]
        s1 = af.wq["set1"][node, h]
        s2 = af.wq["set2"][node, h]
        cross = active & (shard != node % KS)
        _, b2m, big = S.link_bytes(zero, b2i(active), b2i(cross))
        af = af._replace(
            wq_head=af.wq_head.at[node].set(jnp.where(active, (h + 1) % Q, h)),
            wq_len=af.wq_len.at[node].add(-b2i(active)),
            g=af.g + gv(l2_to_mm=active, write_throughs=active,
                        pcie_blocks=cross, bytes_l2_mm=b2m,
                        bytes_inter_gpu=big))
        af, mwts, mrts, ver, gs = mm_write1(af, key, shard, wl, rd, wr,
                                            active)
        # adopt into the node-shared tier (grant lease, node clock advance)
        thA, _, wayA, _, nwA, nrA, ncA = probe1(af.sh, node, s2, key,
                                                mwts, mrts)
        af = af._replace(sh=af.sh._replace(cts=af.sh.cts.at[node].set(
            jnp.where(active, ncA, af.sh.cts[node]))))
        sh2, shg2, sht2, ev1 = install_at(af.sh, af.sh_gseq, af.sh_tick,
                                          node, s2, key, nwA, nrA, ver, gs,
                                          thA, wayA, active)
        # install the adopted lease into the submitting replica
        thB, _, wayB, _, nwB, nrB, ncB = probe1(af.rp, rep, s1, key,
                                                nwA, nrA)
        af = af._replace(
            sh=sh2, sh_gseq=shg2, sh_tick=sht2,
            rp=af.rp._replace(cts=af.rp.cts.at[rep].set(
                jnp.where(active, ncB, af.rp.cts[rep]))),
            r=af.r.at[rep].add(rv(write_throughs=active)))
        rp2, rpg2, rpt2, ev2 = install_at(af.rp, af.rp_gseq, af.rp_tick,
                                          rep, s1, key, nwB, nrB, ver, gs,
                                          thB, wayB, active)
        af = af._replace(
            rp=rp2, rp_gseq=rpg2, rp_tick=rpt2,
            g=af.g + gv(capacity_evictions=b2i(ev1) + b2i(ev2)),
            r=af.r.at[rep].add(rv(capacity_evictions=ev2)))
        entry = (jnp.where(active, key, -1), ver, mwts, mrts, gs)
        return af, entry

    def _flush_node(carry, node, rd, wr, gate=None):
        def cond(c):
            go = c[0].wq_len[node] > 0
            return go if gate is None else go & gate

        def body(c):
            af_, dk, dv, dw, dr_, dg, dc = c
            af_, e = drain1(af_, node, rd, wr, jnp.bool_(True))
            return (af_, dk.at[dc].set(e[0]), dv.at[dc].set(e[1]),
                    dw.at[dc].set(e[2]), dr_.at[dc].set(e[3]),
                    dg.at[dc].set(e[4]), dc + 1)

        return jax.lax.while_loop(cond, body, carry)

    def run(af, xs, rd, wr):
        ldz = jnp.full((LD,), -1, i32)
        negs = jnp.full((), -1, i32)

        def step(af, x):
            kind, rep, node, key, s1, s2, shard, wl = (
                x["kind"], x["rep"], x["node"], x["key"], x["set1"],
                x["set2"], x["shard"], x["wl"])
            is_read = kind == _READ
            is_write = kind == _WRITE
            is_fence = kind == _FENCE
            is_mmw = kind == _MM_WRITE
            is_pub = kind == _PUBLISH
            is_mmr = kind == _MM_READ
            home_miss = shard != node % KS

            # ---- replica probe: serves the read lookup AND the posted
            # write's pending-line placement (ReplicaCache.get / .put)
            rp2, rpt2, th1, h1, way1 = touch(af.rp, af.rp_tick, rep, s1,
                                             key, is_read)
            af = af._replace(rp=rp2, rp_tick=rpt2)
            hit_ver = af.rp.ver[rep, s1, way1]
            hit_gs = af.rp_gseq[rep, s1, way1]
            miss = is_read & ~h1
            coh = miss & th1
            comp = miss & ~th1
            af = af._replace(rp=drop(af.rp, rep, s1, way1, coh))
            # pending line (store-buffer forwarding): wts=rts=cts, ver=-1
            thP, _, wayP, _, _, _, _ = probe1(af.rp, rep, s1, key,
                                              zero, zero)
            cts = af.rp.cts[rep]
            rpP, rpgP, rptP, evP = install_at(
                af.rp, af.rp_gseq, af.rp_tick, rep, s1, key, cts, cts,
                negs, negs, thP, wayP, is_write)
            af = af._replace(rp=rpP, rp_gseq=rpgP, rp_tick=rptP)

            # ---- shared probe (SharedCache.get, only on a replica miss)
            sh2, sht2, th2, h2, way2 = touch(af.sh, af.sh_tick, node, s2,
                                             key, miss)
            af = af._replace(sh=sh2, sh_tick=sht2)
            sh_ver = af.sh.ver[node, s2, way2]
            sh_gs = af.sh_gseq[node, s2, way2]
            sh_wts = af.sh.wts[node, s2, way2]
            sh_rts = af.sh.rts[node, s2, way2]
            coh2 = miss & th2 & ~h2
            af = af._replace(sh=drop(af.sh, node, s2, way2, coh2))

            # ---- MM/TSU access (fabric.read for misses + raw mm_read;
            # mm_write/publish behind a cond — rare on the serving path)
            need_mm = miss & ~h2
            af, fndR, mwtsR, mrtsR, mverR, mgsR = mm_read1(
                af, key, shard, rd, wr, need_mm | is_mmr)
            do_mmw = is_mmw | is_pub

            def _mmw(af):
                return mm_write1(af, key, shard, wl, rd, wr,
                                 jnp.ones((), bool))

            def _mmw_skip(af):
                return af, zero, zero, zero, zero

            if sharded:
                # masked, not cond-gated: every device must execute the
                # same symmetric collective sequence
                af, mwtsW, mrtsW, mverW, mgsW = mm_write1(
                    af, key, shard, wl, rd, wr, do_mmw)
            else:
                af, mwtsW, mrtsW, mverW, mgsW = jax.lax.cond(
                    do_mmw, _mmw, _mmw_skip, af)
            mm_used = (need_mm & fndR) | is_mmr & fndR | do_mmw
            mwts = jnp.where(do_mmw, mwtsW, mwtsR)
            mrts = jnp.where(do_mmw, mrtsW, mrtsR)
            mver = jnp.where(do_mmw, mverW, mverR)
            mgs = jnp.where(do_mmw, mgsW, mgsR)

            # ---- shared-tier install: the read fill (always a victim way
            # — the expired line was dropped) and the publish adopt share
            # one probe+install-math call
            thA, _, wayA, _, nwA, nrA, ncA = probe1(af.sh, node, s2, key,
                                                    mwts, mrts)
            af = af._replace(sh=af.sh._replace(cts=af.sh.cts.at[node].set(
                jnp.where(is_pub, ncA, af.sh.cts[node]))))
            fill_sh = (need_mm & fndR) | is_pub
            sh3, shg3, sht3, evF = install_at(af.sh, af.sh_gseq, af.sh_tick,
                                              node, s2, key, nwA, nrA,
                                              mver, mgs, thA, wayA, fill_sh)
            af = af._replace(sh=sh3, sh_gseq=shg3, sh_tick=sht3)

            # ---- response travelling up to the replica (read path)
            fndF = need_mm & fndR
            resp_found = h2 | fndF
            resp_ver = jnp.where(h2, sh_ver, mver)
            resp_gs = jnp.where(h2, sh_gs, mgs)
            resp_wts = jnp.where(h2, sh_wts, nwA)
            resp_rts = jnp.where(h2, sh_rts, nrA)
            nw1, nr1, _ = S.install_lease(af.rp.cts[rep], resp_wts,
                                          resp_rts)
            rp3, rpg3, rpt3, ev1 = fill(af.rp, af.rp_gseq, af.rp_tick,
                                        rep, s1, key, nw1, nr1,
                                        resp_ver, resp_gs, resp_found)
            af = af._replace(rp=rp3, rp_gseq=rpg3, rp_tick=rpt3)

            # ---- posted write-through: ring push + bounded drain
            t = (af.wq_head[node] + af.wq_len[node]) % Q
            vals = {"key": key, "rep": rep, "wl": wl, "shard": shard,
                    "set1": s1, "set2": s2}
            wq2 = {k: a.at[node, t].set(
                jnp.where(is_write, vals[k], a[node, t]))
                for k, a in af.wq.items()}
            af = af._replace(wq=wq2,
                             wq_len=af.wq_len.at[node].add(b2i(is_write)))
            need_drain = is_write & (af.wq_len[node] > MAXIF)

            def _dr(af):
                return drain1(af, node, rd, wr, jnp.ones((), bool))

            def _dr_skip(af):
                return af, (negs, negs, negs, negs, negs)

            if sharded:
                af, e = drain1(af, node, rd, wr, need_drain)
            else:
                af, e = jax.lax.cond(need_drain, _dr, _dr_skip, af)
            dk = ldz.at[0].set(e[0])
            dv = ldz.at[0].set(e[1])
            dw = ldz.at[0].set(e[2])
            dr_ = ldz.at[0].set(e[3])
            dg = ldz.at[0].set(e[4])
            dc = b2i(need_drain)

            # ---- fence: flush every queue (node order), clocks jump to
            # the global max (rare -> behind a cond; sharded: gated
            # while-loops so the collective schedule stays symmetric)
            def _fence(af):
                carry = (af, ldz, ldz, ldz, ldz, ldz, zero)
                for nd in range(NN):
                    carry = _flush_node(carry, jnp.int32(nd), rd, wr)
                af, fk, fv, fw, fr_, fg, fc = carry
                gmax = jnp.maximum(jnp.max(af.rp.cts), jnp.max(af.sh.cts))
                af = af._replace(
                    rp=af.rp._replace(cts=jnp.full_like(af.rp.cts, gmax)),
                    sh=af.sh._replace(cts=jnp.full_like(af.sh.cts, gmax)))
                return af, (fk, fv, fw, fr_, fg, fc, gmax)

            def _fence_skip(af):
                return af, (dk, dv, dw, dr_, dg, dc, zero)

            if sharded:
                # a fence op is never a write, so (dk..dc) are still the
                # empty drain log here; the gated flush leaves them
                # untouched on non-fence ops (zero loop trips everywhere)
                carry = (af, dk, dv, dw, dr_, dg, dc)
                for nd in range(NN):
                    carry = _flush_node(carry, jnp.int32(nd), rd, wr,
                                        gate=is_fence)
                af, dk, dv, dw, dr_, dg, dc = carry
                gmax_all = jnp.maximum(jnp.max(af.rp.cts),
                                       jnp.max(af.sh.cts))
                gmax = jnp.where(is_fence, gmax_all, zero)
                af = af._replace(
                    rp=af.rp._replace(cts=jnp.where(
                        is_fence, jnp.full_like(af.rp.cts, gmax_all),
                        af.rp.cts)),
                    sh=af.sh._replace(cts=jnp.where(
                        is_fence, jnp.full_like(af.sh.cts, gmax_all),
                        af.sh.cts)))
            else:
                af, (dk, dv, dw, dr_, dg, dc, gmax) = jax.lax.cond(
                    is_fence, _fence, _fence_skip, af)

            # ---- counters: one vector add per block
            b12, b2m, big = S.link_bytes(
                b2i(miss) + b2i(is_write),
                b2i(need_mm) + b2i(is_mmr) + b2i(do_mmw),
                b2i(need_mm & home_miss))
            af = af._replace(
                g=af.g + gv(
                    reads=is_read, writes=is_write, l1_hits=h1, l2_hits=h2,
                    l1_to_l2=b2i(miss) + b2i(is_write), coh_miss_l1=coh,
                    coh_miss_l2=coh2,
                    self_invalidations=b2i(coh) + b2i(coh2),
                    compulsory=comp,
                    l2_to_mm=b2i(need_mm) + b2i(is_mmr) + b2i(do_mmw),
                    pcie_blocks=need_mm & home_miss,
                    write_throughs=do_mmw, fences=is_fence,
                    refetches=resp_found,
                    capacity_evictions=b2i(evP) + b2i(evF) + b2i(ev1),
                    bytes_l1_l2=b12, bytes_l2_mm=b2m, bytes_inter_gpu=big),
                r=af.r.at[rep].add(rv(
                    reads=is_read, writes=is_write, l1_hits=h1, l2_hits=h2,
                    l1_to_l2=b2i(miss) + b2i(is_write), coh_miss_l1=coh,
                    coh_miss_l2=coh2,
                    self_invalidations=b2i(coh) + b2i(coh2),
                    compulsory=comp, refetches=resp_found,
                    # a publish adopt's eviction hits fabric stats only
                    capacity_evictions=(b2i(evP) + b2i(evF & fndF)
                                        + b2i(ev1)))))

            # ---- per-op result record
            found = (is_read & (h1 | resp_found)) | (mm_used & ~is_fence)
            version = jnp.where(
                is_read, jnp.where(h1, hit_ver,
                                   jnp.where(resp_found, resp_ver, -1)),
                jnp.where(mm_used, mver, -1))
            gseq = jnp.where(
                is_read, jnp.where(h1, hit_gs,
                                   jnp.where(resp_found, resp_gs, -1)),
                jnp.where(mm_used, mgs, -1))
            level = jnp.where(
                ~is_read, -1,
                jnp.where(h1, 0, jnp.where(h2, 1, jnp.where(fndF, 2, 3))))
            res = dict(found=b2i(found), version=version, gseq=gseq,
                       level=level, wts=jnp.where(mm_used, mwts, 0),
                       rts=jnp.where(mm_used, mrts, 0),
                       mm_used=b2i(mm_used), gmax=gmax, dlog_key=dk,
                       dlog_ver=dv, dlog_wts=dw, dlog_rts=dr_, dlog_gseq=dg,
                       dcount=dc)
            return af, res

        return jax.lax.scan(step, af, xs)

    if MESH is None:
        # the fabric state is donated: callers always rebind it to the
        # returned carry, and aliasing lets XLA update the tier/TSU
        # arrays in place across batches.  The batched pipeline's sharded
        # engine ALSO lands here: it assembles the full TSU on the lead
        # device with the ONE-collective gather program
        # (``_build_tsu_gather``) and runs this collective-free program
        # against the assembled state (DESIGN.md §12a).
        return jax.jit(run, donate_argnums=0)
    af_spec = _af_pspecs()
    # per-op collective schedule (PIPE="scan"): the TSU-side state is
    # partitioned along the fabric axis, everything else replicated;
    # the per-op results come back replicated (identical on every
    # device by construction)
    return jax.jit(jax.shard_map(run, mesh=MESH,
                                 in_specs=(af_spec, P(), P(), P()),
                                 out_specs=(af_spec, P()), check_vma=False),
                   donate_argnums=0)


@functools.lru_cache(maxsize=8)
def _build_fast_read(mesh=None):
    """Phase 1 of the two-phase batched read (backend.read_batch contract):
    ONE vectorized ``state.tier_probe`` over the whole batch serves every
    replica-tier lease hit — reads under a live lease are pure local
    arithmetic, the paper's serving claim — with sequential touch
    semantics (op i's LRU = tick + its rank among the batch's hits).
    Misses are untouched here; the caller runs them through the exact
    op-scan in op order (phase 2).  Only the replica-tier sub-state flows
    through the call, keeping dispatch overhead off the hot path.

    With ``mesh`` the probe runs as a ``shard_map`` body over the fabric
    axis with fully replicated operands: a lease hit is shard-LOCAL by
    definition (the paper's serving claim — no TSU, no collective, zero
    inter-GPU bytes), so the body contains no communication at all and
    its outputs stay replicated."""
    i32 = jnp.int32

    def fast(rp, rp_gseq, rp_tick, g, r, meta_s1, kids, rep):
        B = kids.shape[0]
        z = jnp.zeros((B,), i32)
        reps = jnp.full((B,), rep, i32)
        s1s = meta_s1[kids]
        th, hit, way, _, _, _, _ = S.tier_probe(rp, reps, s1s, kids, z, z)
        hi = hit.astype(i32)
        rank = jnp.cumsum(hi)            # hit rank (single replica per call)
        w = jnp.where(hit, way, rp.n_ways)
        # .max == sequential .set here: lru values are past ticks, and a
        # duplicate key's later touch carries the larger rank
        lru2 = rp.lru.at[reps, s1s, w].max(rp_tick[rep] + rank)
        ver = rp.ver[reps, s1s, way]
        gseq = rp_gseq[reps, s1s, way]
        # single replica per call -> every counter update is one scalar op
        nh = jnp.sum(hi)
        tick2 = rp_tick.at[rep].add(nh)
        g2 = g.at[_GI["reads"]].add(nh).at[_GI["l1_hits"]].add(nh)
        r2 = r.at[rep, _RI["reads"]].add(nh)
        r2 = r2.at[rep, _RI["l1_hits"]].add(nh)
        # only the MODIFIED arrays travel back — the untouched tier fields
        # stay resident — and the per-op outputs are packed into one
        # transfer, keeping the hot-path call payload minimal
        return jnp.stack([hi, ver, gseq]), lru2, tick2, g2, r2

    if mesh is None:
        return jax.jit(fast)
    return jax.jit(jax.shard_map(fast, mesh=mesh, in_specs=(P(),) * 8,
                                 out_specs=(P(),) * 5, check_vma=False))


@functools.lru_cache(maxsize=32)
def _build_miss_run(W1, W2, KS):
    """Phase 2 of the two-phase batched read, jitted: the vectorized miss
    pass (``pipeline.make_miss_pass``) — ALL conflict-free rounds of the
    miss subset in one call (one ``lax.scan`` over the round masks, the
    fabric state donated so XLA updates it in place), one batched probe
    per tier, ONE batched TSU grant and one batched fill per tier per
    round.  The program is collective-free; the sharded engine brackets
    it with the gather/scatter exchange (``ArrayFabric._xin``/``_xout``),
    so a miss-heavy sharded serving batch costs O(1) collectives no
    matter how many rounds or misses."""
    return jax.jit(P_.make_miss_pass(W1, W2, KS), donate_argnums=0)


@functools.lru_cache(maxsize=32)
def _build_write_run(W1, W2, KS, NN, NR, Q, MAXIF):
    """The batched write pass, jitted: ALL conflict-free rounds of a
    posted-write batch in one call (``pipeline.make_write_pass`` — one
    ``lax.scan`` over the round masks, the fabric state donated), the
    lane-static drain schedule resolved on the host
    (``pipeline.write_schedule``), ONE batched TSU write-through grant
    per round (``state.tsu_commit_write_batch``) and prefix-sum
    clock/LRU sequencing (DESIGN.md §11).  Collective-free; the sharded
    engine brackets it with the gather/scatter exchange, so a republish
    storm costs O(1) collectives no matter how many writes or rounds."""
    return jax.jit(P_.make_write_pass(W1, W2, KS, NN, NR, Q, MAXIF),
                   donate_argnums=0)


@functools.lru_cache(maxsize=32)
def _build_fence_run(W1, W2, KS, NN, NR, Q):
    """The vectorized fence pass, jitted (``pipeline.make_fence_pass``):
    drain EVERY node's queue over conflict-free rounds with the
    lane-static schedule from ``pipeline.fence_schedule``, then jump all
    client clocks to the global max (§11b).  Collective-free; used by the
    sharded batched engine so the serving loop's fences stop paying the
    op-scan's per-op dispatch (the single-device ``ArrayFabric`` keeps
    the op-scan fence as the reference path)."""
    return jax.jit(P_.make_fence_pass(W1, W2, KS, NN, NR, Q),
                   donate_argnums=0)


@functools.lru_cache(maxsize=8)
def _build_tsu_gather(MESH):
    """The batched engine's per-batch grant exchange, jitted: pack each
    device's owned TSU rows (``state.pack_tsu``) and assemble the full
    shard-major buffer on every device with ONE ``owner_gather`` — the
    batch's single collective — returning the unpacked full-table leaves
    (replicated; the engine adopts the lead device's copy).  This is the
    one program the O(1)-collectives-per-batch pin traces for the dev0
    pass engine: the passes themselves are collective-free."""
    F = P("fabric")

    def body(tsu, ver, gseq, seq, nseq):
        return S.unpack_tsu(S.owner_gather(
            S.pack_tsu(tsu, ver, gseq, seq, nseq), "fabric"))

    return jax.jit(jax.shard_map(body, mesh=MESH, in_specs=(F,) * 5,
                                 out_specs=(P(),) * 5, check_vma=False))


class ArrayFabric(FabricBackend):
    """The array-native fabric: ``FabricBackend`` over one jitted op-scan.

    ``apply(ops)`` encodes the batch into int32 op arrays (keys are interned
    to dense ids; set indexes and shard routes precomputed with the same
    ``stable_hash`` the host stores use), runs the scan, then replays the
    returned plan on the host-side payload map.  Batches are padded to
    power-of-two lengths so compilations are reused across batch sizes.
    """

    def __init__(self, cfg: FabricConfig = FabricConfig(),
                 n_nodes: int = 1, replicas_per_node: int = 1, mesh=None,
                 pipeline: str = "batched"):
        self.cfg = cfg = _bounded(cfg)
        if pipeline not in PIPELINES:
            raise ValueError(f"pipeline must be one of {PIPELINES}, "
                             f"got {pipeline!r}")
        self.pipeline = pipeline
        self.n_nodes = n_nodes
        self.n_replicas = n_nodes * replicas_per_node
        self._rpn = replicas_per_node
        self._S1 = max(1, cfg.replica_sets)
        self._W1 = max(1, cfg.replica_ways)
        self._S2 = max(1, cfg.shared_sets)
        self._W2 = max(1, cfg.shared_ways)
        self._KS = cfg.n_shards
        self._CAP = cfg.tsu_capacity
        self._Q = cfg.max_in_flight + 2
        self._LD = n_nodes * cfg.max_in_flight + 1
        self.mesh = mesh                 # 1-axis "fabric" mesh or None
        if mesh is not None and self._KS % int(mesh.devices.size):
            raise ValueError(
                f"n_shards={self._KS} must be divisible by the fabric "
                f"mesh's {int(mesh.devices.size)} devices")
        # the batched pipeline runs every program on the lead device
        # against gather-assembled state (the dev0 pass engine, DESIGN.md
        # §12a), so its op-scan / passes are the collective-free MESH=None
        # programs — shared compilations with the single-device fabric.
        # Only pipeline="scan" keeps the per-op shard_map schedule.
        run_mesh = mesh if (mesh is not None and pipeline == "scan") \
            else None
        self._run = _build_run(self._S1, self._W1, self._S2, self._W2,
                               self._KS, self._CAP, n_nodes,
                               self.n_replicas, self._Q, cfg.max_in_flight,
                               self._LD, run_mesh, "scan")
        self._miss_run = (_build_miss_run(self._W1, self._W2, self._KS)
                          if pipeline == "batched" else None)
        self._write_run = (_build_write_run(self._W1, self._W2, self._KS,
                                            n_nodes, self.n_replicas,
                                            self._Q, cfg.max_in_flight)
                           if pipeline == "batched" else None)
        self._fence_run = (_build_fence_run(self._W1, self._W2, self._KS,
                                            n_nodes, self.n_replicas,
                                            self._Q)
                           if pipeline == "batched" else None)
        # the sharded batched engine: ONE packed owner_gather per batch
        # assembles the full TSU table, the passes run on the lead device,
        # and `_xout` scatters the updated TSU rows back to their owners
        # then immediately dispatches the NEXT batch's gather — the
        # exchange double-buffers under the current batch's host decode
        # (ISSUE 8 tentpole, DESIGN.md §12a)
        if mesh is not None and pipeline == "batched":
            self._gather_run = _build_tsu_gather(mesh)
            # the mesh's own lead device: a mesh built from other devices
            # must not run its passes off-mesh
            self._dev0 = mesh.devices.flat[0]
            f3 = named_sharding(mesh, (self._KS, 1, self._CAP + 1),
                                ("fabric_shard", None, None))
            f1 = named_sharding(mesh, (self._KS,), ("fabric_shard",))
            self._tsu_shardings = (f3, f3, f3, f3, f1)
        else:
            self._gather_run = None
        self._tsu_full = None
        self._af = self._init_af()
        # host-side payload plumbing (the arrays decide; this only ships)
        self._keys: Dict = {}
        self._key_list: List = []
        self._meta = np.zeros((64, 3), np.int32)    # kid -> set1, set2, shard
        self._vals: Dict[int, object] = {}          # gseq -> value
        self._pending: Dict[Tuple[int, int], object] = {}
        self._pending_n: Dict[Tuple[int, int], int] = {}   # in-flight count
        self._qmirror = [collections.deque() for _ in range(n_nodes)]
        # bounded on BOTH backends with the same cap, so parity-compared
        # logs truncate identically (oracle traces are far shorter)
        self.grant_log = collections.deque(maxlen=GRANT_LOG_LEN)
        self._fast_read = _build_fast_read(run_mesh)
        self._meta_dev = None           # device-side kid -> set1 table
        self._fast_read_batches = 0     # all-hit batches (FabricStats field)
        self._write_batches = 0         # non-empty write_batch calls
        self._writes_since_prune = 0

    def _init_af(self) -> _AF:
        i32 = jnp.int32
        z = lambda *s: jnp.zeros(s, i32)
        neg = lambda *s: jnp.full(s, -1, i32)
        Nn, R = self.n_nodes, self.n_replicas
        af = _AF(
            rp=S.init_tier(R, self._S1, self._W1),
            rp_gseq=neg(R, self._S1, self._W1 + 1), rp_tick=z(R),
            sh=S.init_tier(Nn, self._S2, self._W2),
            sh_gseq=neg(Nn, self._S2, self._W2 + 1), sh_tick=z(Nn),
            tsu=S.init_tsu(self._KS, 1, self._CAP),
            tsu_ver=z(self._KS, 1, self._CAP + 1),
            tsu_gseq=neg(self._KS, 1, self._CAP + 1),
            tsu_seq=z(self._KS, 1, self._CAP + 1), tsu_nseq=z(self._KS),
            gseq_next=jnp.zeros((), i32),
            wq={k: z(Nn, self._Q) for k in
                ("key", "rep", "wl", "shard", "set1", "set2")},
            wq_head=z(Nn), wq_len=z(Nn),
            g=z(len(_G_KEYS)), r=z(R, len(_R_KEYS)),
        )
        if self.mesh is not None:
            f3 = named_sharding(self.mesh, (self._KS, 1, self._CAP + 1),
                                ("fabric_shard", None, None))
            f1 = named_sharding(self.mesh, (self._KS,), ("fabric_shard",))
            if self.pipeline == "batched":
                # dev0 pass engine: only the TSU — the state of record the
                # per-batch gather assembles — lives on the mesh; every
                # other leaf stays on the lead device where the passes run
                af = jax.device_put(af, self._dev0)._replace(
                    tsu=jax.device_put(af.tsu, f3),
                    tsu_ver=jax.device_put(af.tsu_ver, f3),
                    tsu_gseq=jax.device_put(af.tsu_gseq, f3),
                    tsu_seq=jax.device_put(af.tsu_seq, f3),
                    tsu_nseq=jax.device_put(af.tsu_nseq, f1))
            else:
                # per-op schedule: lay the state out per _af_pspecs BEFORE
                # the first run — TSU rows land on their owning devices
                # (sharding.py rules map the shard-major dims onto the
                # fabric axis), the rest replicated
                rep = NamedSharding(self.mesh, P())
                af = jax.device_put(af, _AF(
                    rp=rep, rp_gseq=rep, rp_tick=rep, sh=rep, sh_gseq=rep,
                    sh_tick=rep, tsu=f3, tsu_ver=f3, tsu_gseq=f3,
                    tsu_seq=f3, tsu_nseq=f1, gseq_next=rep, wq=rep,
                    wq_head=rep, wq_len=rep, g=rep, r=rep))
        return af

    # --------------------------------------------------- grant exchange
    def _dispatch_gather(self) -> None:
        af = self._af
        self._tsu_full = self._gather_run(af.tsu, af.tsu_ver,
                                          af.tsu_gseq, af.tsu_seq,
                                          af.tsu_nseq)

    def _xin(self) -> _AF:
        """Enter a device pass: hand it the lead-device view of the
        fabric state.  On the sharded batched engine the TSU leaves are
        the gather-assembled full table — prefetched by the previous
        ``_xout`` (dispatched here only on the very first batch) and
        adopted as zero-copy lead-device views of the replicated gather
        outputs.  Identity on the single-device fabric."""
        if self._gather_run is None:
            return self._af
        if self._tsu_full is None:
            self._dispatch_gather()
        full = self._tsu_full
        self._tsu_full = None
        dev0 = self._dev0

        def local(x):
            # replicated over the mesh, so the lead device holds a copy
            (data,) = [s.data for s in x.addressable_shards
                       if s.device == dev0]
            return data

        tsu, ver, gseq, seq, nseq = jax.tree_util.tree_map(local, full)
        return self._af._replace(tsu=tsu, tsu_ver=ver, tsu_gseq=gseq,
                                 tsu_seq=seq, tsu_nseq=nseq)

    def _xout(self, af: _AF) -> None:
        """Leave a device pass: adopt its output state.  On the sharded
        batched engine the updated TSU rows scatter back to their owning
        devices (async) and the NEXT batch's gather is dispatched
        immediately, so the one collective per batch overlaps this
        batch's host-side decode instead of sitting on the critical
        path."""
        if self._gather_run is None:
            self._af = af
            return
        tsu, ver, gseq, seq, nseq = jax.device_put(
            (af.tsu, af.tsu_ver, af.tsu_gseq, af.tsu_seq, af.tsu_nseq),
            self._tsu_shardings)
        self._af = af._replace(tsu=tsu, tsu_ver=ver, tsu_gseq=gseq,
                               tsu_seq=seq, tsu_nseq=nseq)
        self._dispatch_gather()

    # ------------------------------------------------------------- keys
    def _kid(self, key) -> int:
        kid = self._keys.get(key)
        if kid is None:
            kid = len(self._key_list)
            self._keys[key] = kid
            self._key_list.append(key)
            if kid >= self._meta.shape[0]:
                self._meta = np.concatenate(
                    [self._meta, np.zeros_like(self._meta)], axis=0)
            h = stable_hash(key)
            self._meta[kid] = (h % self._S1, h % self._S2, h % self._KS)
            self._meta_dev = None        # device copy is stale
        return kid

    # ------------------------------------------------------------ apply
    def apply(self, ops: Sequence[Op]):
        B0 = len(ops)
        if B0 == 0:
            return []
        B = max(8, _next_pow2(B0))
        with obs.span("fabric.pack", n_ops=B0, padded=B):
            enc = {k: np.zeros((B,), np.int32) for k in
                   ("kind", "rep", "node", "key", "set1", "set2", "shard",
                    "wl")}
            for i, op in enumerate(ops):
                enc["kind"][i] = _KIND[op.kind]
                if op.kind == "fence":
                    continue
                kid = self._kid(op.key)
                s1, s2, shard = self._meta[kid]
                rep = op.replica
                node = (op.node if op.kind == "publish"
                        else rep // self._rpn)
                enc["rep"][i] = rep
                enc["node"][i] = node
                enc["key"][i] = kid
                enc["set1"][i] = s1
                enc["set2"][i] = s2
                enc["shard"][i] = shard
                enc["wl"][i] = -1 if op.wr_lease is None else op.wr_lease
        with obs.span("fabric.exchange"):
            xs = {k: jnp.asarray(v) for k, v in enc.items()}
            af = self._xin()
        with obs.span("fabric.scan", n_ops=B0):
            af, res = self._run(af, xs,
                                jnp.int32(self.cfg.rd_lease),
                                jnp.int32(self.cfg.wr_lease))
            self._xout(af)
            obs.fence(res, "fabric.scan.device")
        with obs.span("fabric.decode", n_ops=B0):
            res = jax.device_get(res)
            out = [(op, self._decode(op, res, i))
                   for i, op in enumerate(ops)]
        if self._writes_since_prune >= _PRUNE_EVERY:
            with obs.span("fabric.donate"):
                self.prune_payloads()   # after decode: results already out
        return out

    def prune_payloads(self) -> None:
        """Drop payload versions no longer referenced by any device-side
        line or TSU entry.  HostFabric sheds values implicitly when a dict
        entry / cache line is evicted; here payloads are named by gseq
        handles, so an explicit sweep against the live handle set keeps
        host memory bounded on long-running serving paths."""
        live = set()
        for a in (self._af.rp_gseq, self._af.sh_gseq, self._af.tsu_gseq):
            live.update(np.unique(np.asarray(a)).tolist())
        self._vals = {g: v for g, v in self._vals.items() if g in live}
        self._writes_since_prune = 0

    def _drains(self, res, i, node: Optional[int] = None) -> None:
        """Replay the op's drain log on the payload map + grant log.  A
        write op drains its own node's queue; a fence drains every queue in
        node order (node=None -> pop the first non-empty mirror)."""
        for j in range(int(res["dcount"][i])):
            dk = int(res["dlog_key"][i][j])
            nd = (node if node is not None else
                  next(n for n in range(self.n_nodes) if self._qmirror[n]))
            mk, mval, mrep, _mwl = self._qmirror[nd].popleft()
            assert mk == dk, "queue mirror diverged from the in-scan ring"
            self._vals[int(res["dlog_gseq"][i][j])] = mval
            self._writes_since_prune += 1
            # last in-flight write for (rep, key) drained: the replica line
            # now carries a real gseq, so the store-buffer copy can go
            n = self._pending_n.get((mrep, mk), 0) - 1
            if n <= 0:
                self._pending_n.pop((mrep, mk), None)
                self._pending.pop((mrep, mk), None)
            else:
                self._pending_n[(mrep, mk)] = n
            self.grant_log.append((self._key_list[dk],
                                   int(res["dlog_wts"][i][j]),
                                   int(res["dlog_rts"][i][j]),
                                   int(res["dlog_ver"][i][j])))

    def _read_result(self, kid: int, replica: int, found, version, gseq):
        """Decode one read op's device outputs into the API result: None
        on a miss, store-buffer forwarding (version < 0) of a posted
        write, else payload + version.  The ONE read-decode shared by the
        op-scan path and the batched miss pass (the phase-1 hit loop
        inlines the same rule for throughput)."""
        if not found:
            return None
        ver = int(version)
        if ver < 0:
            return self._pending[(replica, kid)], None
        return self._vals[int(gseq)], ver

    def _decode(self, op: Op, res, i):
        kind = op.kind
        if kind == "read":
            if res["mm_used"][i]:
                self.grant_log.append((op.key, int(res["wts"][i]),
                                       int(res["rts"][i]),
                                       int(res["version"][i])))
            return self._read_result(self._keys[op.key], op.replica,
                                     res["found"][i], res["version"][i],
                                     res["gseq"][i])
        if kind == "write":
            kid = self._keys[op.key]
            self._pending[(op.replica, kid)] = op.value
            self._pending_n[(op.replica, kid)] = self._pending_n.get(
                (op.replica, kid), 0) + 1
            node = op.replica // self._rpn
            self._qmirror[node].append(
                (kid, op.value, op.replica,
                 -1 if op.wr_lease is None else op.wr_lease))
            self._drains(res, i, node=node)
            return None
        if kind == "fence":
            self._drains(res, i)
            return int(res["gmax"][i])
        if kind in ("mm_write", "publish"):
            gs = int(res["gseq"][i])
            self._vals[gs] = op.value
            self._writes_since_prune += 1
            g = (op.key, int(res["wts"][i]), int(res["rts"][i]),
                 int(res["version"][i]))
            self.grant_log.append(g)
            if kind == "mm_write":
                return g[1], g[2], g[3]
            return g[1], g[2]
        if kind == "mm_read":
            if not res["found"][i]:
                return None
            g = (op.key, int(res["wts"][i]), int(res["rts"][i]),
                 int(res["version"][i]))
            self.grant_log.append(g)
            return (self._vals[int(res["gseq"][i])], g[3], g[1], g[2])
        raise ValueError(f"unknown op kind {kind!r}")

    # ------------------------------------------------------------ batched
    def peek(self, key, replica: int = 0) -> bool:
        kid = self._keys.get(key)
        if kid is None:
            return False
        s1 = self._meta[kid][0]
        tags = np.asarray(self._af.rp.tag[replica, s1])[:-1]
        w = np.nonzero(tags == kid)[0]
        if w.size == 0:
            return False
        rts = int(np.asarray(self._af.rp.rts[replica, s1])[w[0]])
        return bool(protocol.valid(int(np.asarray(self._af.rp.cts[replica])),
                                   rts))

    def read_batch(self, keys: Sequence, replica: int = 0):
        """The two-phase batched read (backend contract), vectorized:
        phase 1 serves every replica-tier lease hit with ONE
        ``state.tier_probe`` call over the whole batch; phase 2 serves
        the miss subset with the vectorized miss pass (the batched grant
        pipeline, DESIGN.md §9) — conflict-free rounds, one batched TSU
        grant per round — falling back to the exact op-scan under
        ``pipeline="scan"`` or when the subset is so conflict-ridden the
        round budget (``max(_MIN_ROUND_BUDGET, misses // 4)``) is blown."""
        return self.read_batch_async(keys, replica).result()

    def read_batch_async(self, keys: Sequence, replica: int = 0):
        """The overlapped batched read (backend contract): everything
        device-side — the phase-1 probe, the miss pass, and on the
        sharded engine the NEXT batch's grant exchange — is dispatched
        before this returns; only the miss subset's host-side payload
        decode waits in the handle.  A serving loop
        (``Server.serve_stream``) dispatches batch N+1 while batch N's
        decode is still pending, hiding the exchange + decode latency
        under device compute."""
        if not keys:
            return ReadBatchHandle(lambda: [])
        B = len(keys)
        with obs.span("fabric.pack", n_ops=B):
            keymap = self._keys
            try:
                kids = [keymap[k] for k in keys]  # hot path: interned keys
            except KeyError:
                kids = [self._kid(k) for k in keys]
            kids_np = np.asarray(kids, np.int32)
            if self._meta_dev is None:
                # whole table at its (power-of-two) capacity: stable shapes
                self._meta_dev = jnp.asarray(self._meta[:, 0])
        with obs.span("fabric.fast_probe", n_ops=B):
            packed, lru2, tick2, g2, r2 = self._fast_read(
                self._af.rp, self._af.rp_gseq, self._af.rp_tick, self._af.g,
                self._af.r, self._meta_dev, jnp.asarray(kids_np),
                np.int32(replica))
            obs.fence(packed, "fabric.fast_probe.device")
        with obs.span("fabric.donate"):
            self._af = self._af._replace(rp=self._af.rp._replace(lru=lru2),
                                         rp_tick=tick2, g=g2, r=r2)
        with obs.span("fabric.decode", n_ops=B):
            packed = np.asarray(packed)
            hit = packed[0].astype(bool)
            ver, gseq = packed[1], packed[2]
            vals, pend = self._vals, self._pending
            if hit.all():
                self._fast_read_batches += 1
                ready = [(vals[g], v) if v >= 0
                         else (pend[(replica, k)], None)
                         for k, v, g in zip(kids, ver.tolist(),
                                            gseq.tolist())]
                return ReadBatchHandle(lambda: ready)
            out: List = [None] * B
            for i in np.nonzero(hit)[0]:
                v = int(ver[i])
                out[i] = ((pend[(replica, kids[i])], None) if v < 0
                          else (vals[int(gseq[i])], v))
            miss = np.nonzero(~hit)[0]
        with obs.span("fabric.miss_pass", misses=int(miss.size)):
            decode = (self._read_misses_dispatch(keys, kids_np, miss,
                                                 replica)
                      if self.pipeline == "batched" else None)
        if decode is None:          # scan pipeline / round-budget bail
            res = self.apply([Op("read", keys[i], replica=replica)
                              for i in miss])
            served = [r for _, r in res]
            for j, i in enumerate(miss):
                out[i] = served[j]
            return ReadBatchHandle(lambda: out)

        def finish():
            with obs.span("fabric.miss_pass", misses=int(miss.size)):
                served = decode()
            for j, i in enumerate(miss):
                out[i] = served[j]
            return out

        return ReadBatchHandle(finish)

    def _read_misses_dispatch(self, keys, kids_np, miss, replica):
        """Dispatch the miss subset through the vectorized miss pass:
        graph-colored conflict-free rounds (`pipeline.conflict_rounds`),
        ONE jitted pass over the padded subset.  Returns a decode
        closure that resolves results — grant-log appends and payload
        lookups — in op order (the deferred half of
        ``read_batch_async``), or None to signal the op-scan fallback
        when the subset is too conflict-ridden to pay off."""
        m = miss.size
        with obs.span("fabric.pack", misses=int(m)):
            kids_m = kids_np[miss]
            meta = self._meta[kids_m]
            rounds = P_.conflict_rounds(kids_m, meta[:, 0], meta[:, 1])
            if len(rounds) > max(_MIN_ROUND_BUDGET, m // 4):
                return None
            # coarse pow2 buckets (M >= 32 lanes, R >= 4 rounds): the padded
            # lanes/rounds are fully masked no-ops, and near-miss shape churn
            # (15 vs 17 misses, 1 vs 2 rounds) must not trigger recompiles on
            # the serving hot path
            M = max(32, _next_pow2(m))
            R = max(4, _next_pow2(len(rounds)))
            masks = P_.round_masks(rounds, R, M)
            ops = np.zeros((4, M), np.int32)
            ops[0, :m] = kids_m
            ops[1, :m] = meta[:, 0]
            ops[2, :m] = meta[:, 1]
            ops[3, :m] = meta[:, 2]
            node = replica // self._rpn
        with obs.span("fabric.exchange", lanes=M, rounds=R):
            args = (jnp.asarray(ops), jnp.asarray(masks))
            af = self._xin()
        with obs.span("fabric.scan", misses=int(m)):
            af, res = self._miss_run(
                af, *args, np.int32(replica), np.int32(node),
                jnp.int32(self.cfg.rd_lease), jnp.int32(self.cfg.wr_lease))
            self._xout(af)
            obs.fence(res, "fabric.scan.device")
        def decode():
            with obs.span("fabric.decode", misses=int(m)):
                r = np.asarray(jax.device_get(res))  # packed [7, M] block
                fields = dict(zip(P_.RES_FIELDS, r))
                out: List = []
                for j, i in enumerate(miss):
                    if fields["mm_used"][j]:
                        self.grant_log.append(
                            (keys[i], int(fields["wts"][j]),
                             int(fields["rts"][j]),
                             int(fields["version"][j])))
                    out.append(self._read_result(int(kids_m[j]), replica,
                                                 fields["found"][j],
                                                 fields["version"][j],
                                                 fields["gseq"][j]))
            return out

        return decode

    def _note_write_batch(self) -> None:
        self._write_batches += 1

    def write_batch(self, items, replica: int = 0, wr_lease=None) -> None:
        """Batched posted writes (backend contract), vectorized: the whole
        storm runs through the batched write pass (DESIGN.md §11) —
        graph-colored conflict-free rounds with the lane-static drain
        schedule (``pipeline.write_schedule``), ONE batched TSU
        write-through grant per round, and on the sharded fabric ONE
        packed collective per batch — falling back
        to the exact op-scan under ``pipeline="scan"`` or when the batch
        is so conflict-ridden the round budget
        (``max(_MIN_ROUND_BUDGET, writes // 2)``) is blown."""
        items = list(items)
        if not items:
            return
        self._note_write_batch()
        served = False
        if self._write_run is not None:
            with obs.span("fabric.write_pass", n_ops=len(items)):
                served = self._write_batch_batched(items, replica, wr_lease)
        if not served:
            self.apply([Op("write", k, v, replica=replica,
                           wr_lease=wr_lease) for k, v in items])

    def _write_batch_batched(self, items, replica, wr_lease) -> bool:
        """Serve a posted-write batch with the vectorized write pass:
        resolve the lane-static drain schedule and graph-colored rounds
        on the host (``pipeline.write_schedule``), run all rounds as ONE
        jitted pass over the padded batch, then replay the returned drain
        log — payload handoffs and grant-log appends — in op order via
        the op-scan's own ``_drains`` decoder.  Returns False to signal
        the op-scan fallback when the batch is too conflict-ridden."""
        B = len(items)
        node = replica // self._rpn
        with obs.span("fabric.pack", n_ops=B):
            kids = np.asarray([self._kid(k) for k, _ in items], np.int32)
            meta = self._meta[kids]
            wl = -1 if wr_lease is None else wr_lease
            pending = [(k, *self._meta[k].tolist(), r, w)
                       for k, _, r, w in self._qmirror[node]]
            rounds, sched = P_.write_schedule(
                kids, meta[:, 0], meta[:, 1], meta[:, 2], replica, wl,
                pending, self.cfg.max_in_flight)
            if len(rounds) > max(_MIN_ROUND_BUDGET, B // 2):
                return False
            M = max(32, _next_pow2(B))
            R = max(4, _next_pow2(len(rounds)))
            masks = P_.round_masks(rounds, R, M)
            ops = np.zeros((4, M), np.int32)
            ops[0, :B] = kids
            ops[1, :B] = meta[:, 0]
            ops[2, :B] = meta[:, 1]
            ops[3, :B] = meta[:, 2]
            sched = np.pad(sched, ((0, 0), (0, M - B)))
        with obs.span("fabric.exchange", lanes=M, rounds=R):
            args = (jnp.asarray(ops), jnp.asarray(sched),
                    jnp.asarray(masks))
            af = self._xin()
        with obs.span("fabric.scan", n_ops=B):
            af, res = self._write_run(
                af, *args, np.int32(replica), np.int32(node),
                jnp.int32(wl), jnp.int32(self.cfg.rd_lease),
                jnp.int32(self.cfg.wr_lease))
            self._xout(af)
            obs.fence(res, "fabric.scan.device")
        with obs.span("fabric.decode", n_ops=B):
            res = np.asarray(jax.device_get(res))  # packed [6, M] block
            f = dict(zip(P_.WRITE_RES_FIELDS, res))
            # the drain decoder reads per-op drain-log ROWS; a write op
            # drains at most once, so each lane is a one-column row
            rd = {"dcount": f["dcount"]}
            rd.update({k: f[k][:, None] for k in P_.WRITE_RES_FIELDS[1:]})
            for i, (k, v) in enumerate(items):
                kid = int(kids[i])
                self._pending[(replica, kid)] = v
                self._pending_n[(replica, kid)] = self._pending_n.get(
                    (replica, kid), 0) + 1
                self._qmirror[node].append((kid, v, replica, wl))
                self._drains(rd, i, node=node)
        if self._writes_since_prune >= _PRUNE_EVERY:
            with obs.span("fabric.donate"):
                self.prune_payloads()
        return True

    # ------------------------------------------------------------ scalar
    def read(self, key, replica: int = 0):
        return self.apply([Op("read", key, replica=replica)])[0][1]

    def write(self, key, value, replica: int = 0, wr_lease=None) -> None:
        self.apply([Op("write", key, value, replica=replica,
                       wr_lease=wr_lease)])

    def fence(self) -> int:
        """Drain every node's posted-write queue, then jump all client
        clocks to the global max (§11b).  On the sharded batched engine
        the fence runs as the dedicated vectorized fence pass (one jitted
        call, one gather collective) instead of paying the op-scan's
        per-drain dispatch; the single-device fabric keeps the op-scan
        fence as the bit-identical reference path (both are
        parity-checked against ``HostFabric``)."""
        if self._gather_run is not None and self._fence_run is not None:
            out = self._fence_batched()
            if out is not None:
                return out
        return self.apply([Op("fence")])[0][1]

    def _fence_batched(self) -> Optional[int]:
        """Serve a fence with the vectorized fence pass: every queued
        entry (all nodes, node-major FIFO — the host drain order) becomes
        one schedule lane, rounds are conflict-free segments
        (``pipeline.fence_schedule``), and the drain log replays through
        the op-scan's own ``_drains`` decoder.  Returns None to signal
        the op-scan fallback when the drain set is too conflict-ridden."""
        entries = []
        for nd in range(self.n_nodes):
            for kid, _v, rep, wl in self._qmirror[nd]:
                s1, s2, shard = self._meta[kid]
                entries.append((kid, s1, s2, shard, rep, wl, nd))
        D0 = len(entries)
        with obs.span("fabric.pack", n_ops=D0):
            rounds, sched = P_.fence_schedule(entries)
            if len(rounds) > max(_MIN_ROUND_BUDGET, max(1, D0) // 2):
                return None
            D = max(8, _next_pow2(max(1, D0)))
            R = max(4, _next_pow2(len(rounds)))
            sched = np.pad(sched, ((0, 0), (0, D - D0)))
            masks = P_.round_masks(rounds, R, D)
        with obs.span("fabric.exchange", lanes=D, rounds=R):
            args = (jnp.asarray(sched), jnp.asarray(masks))
            af = self._xin()
        with obs.span("fabric.scan", n_ops=D0):
            af, res, gmax = self._fence_run(
                af, *args, jnp.int32(self.cfg.rd_lease),
                jnp.int32(self.cfg.wr_lease))
            self._xout(af)
            obs.fence(res, "fabric.scan.device")
        with obs.span("fabric.decode", n_ops=D0):
            res = np.asarray(jax.device_get(res))   # packed [6, D] block
            f = dict(zip(P_.WRITE_RES_FIELDS, res))
            # ONE fence op draining D0 entries: the decoder reads per-op
            # drain-log rows, so the whole lane axis is row 0
            rd = {"dcount": np.asarray([D0], np.int32)}
            rd.update({k: f[k][None, :]
                       for k in P_.WRITE_RES_FIELDS[1:]})
            self._drains(rd, 0)
        if self._writes_since_prune >= _PRUNE_EVERY:
            with obs.span("fabric.donate"):
                self.prune_payloads()
        return int(jax.device_get(gmax))

    def mm_write(self, key, value, wr_lease=None):
        return self.apply([Op("mm_write", key, value,
                              wr_lease=wr_lease)])[0][1]

    def publish(self, key, value, node: int = 0, wr_lease=None):
        return self.apply([Op("publish", key, value, node=node,
                              wr_lease=wr_lease)])[0][1]

    def mm_read(self, key):
        return self.apply([Op("mm_read", key)])[0][1]

    # ------------------------------------------------------------ views
    def memts(self, key) -> int:
        kid = self._keys.get(key)
        if kid is None:
            return 0
        shard = self._meta[kid][2]
        tags = np.asarray(self._af.tsu.tag[shard, 0])
        hit = np.nonzero(tags == kid)[0]
        if hit.size == 0:
            return 0
        return int(np.asarray(self._af.tsu.memts[shard, 0])[hit[0]])

    @property
    def fast_read_batches(self) -> int:
        """All-hit batches served by phase 1 alone — a FabricStats field
        (reported by ``stats()`` so backend equality assertions cover it);
        this accessor is kept for telemetry callers."""
        return self._fast_read_batches

    def stats(self) -> Dict[str, int]:
        g = np.asarray(jax.device_get(self._af.g))
        out = {k: int(g[i]) for i, k in enumerate(_G_KEYS)}
        out["wb_evictions"] = 0
        out["inval_msgs"] = 0
        out["fast_read_batches"] = self._fast_read_batches
        out["write_batches"] = self._write_batches
        return out

    def replica_stats(self, replica: int = 0) -> Dict[str, int]:
        r = np.asarray(jax.device_get(self._af.r))[replica]
        out = {k: 0 for k in self.stats()}
        out.update({k: int(r[i]) for i, k in enumerate(_R_KEYS)})
        return out


class ShardedArrayFabric(ArrayFabric):
    """The mesh-placed fabric: TSU shards on devices along a ``fabric`` axis.

    HALCONE's TSU is physically distributed — one timestamp storage unit
    per HBM stack, coherence actions executed local to the memory they
    guard.  This backend realizes that placement: the ``[n_shards,
    capacity]`` TSU table (plus the per-shard grant sequencers and
    version/gseq side arrays) is partitioned over the ``fabric`` mesh axis
    with ``NamedSharding`` and the op-scan runs as a ``jax.shard_map``
    body.  Under the default batched grant pipeline the owned
    TSU rows are exchanged as ONE packed collective per batch (DESIGN.md
    §9); under ``pipeline="scan"`` each op's TSU transition executes only
    on its key's owning device and the grant hops back per scan step (the
    PR-4 schedule).  Either way the protocol-level cross-shard traffic is
    what the ``bytes_inter_gpu`` counter measures (Fig. 10) — it counts
    home-shard misses, not mesh messages, so it is identical across
    pipelines and mesh sizes.  Client tiers and the write-queue rings
    stay replicated across the axis.

    Still a ``FabricBackend``, still bit-identical to ``HostFabric`` and
    to the single-device ``ArrayFabric`` on any op trace
    (tests/test_fabric_parity.py runs the suite on a forced 8-device host
    mesh).  ``n_shards`` must be divisible by the mesh size; by default
    the largest dividing device count is used (``launch.mesh.
    make_fabric_mesh``), so a 1-device host degenerates to the
    single-device layout under the same shard_map entry point.
    """

    def __init__(self, cfg: FabricConfig = FabricConfig(),
                 n_nodes: int = 1, replicas_per_node: int = 1,
                 mesh=None, devices=None, pipeline: str = "batched"):
        cfg = _bounded(cfg)
        if mesh is None:
            from repro.launch.mesh import make_fabric_mesh
            mesh = make_fabric_mesh(n_shards=cfg.n_shards, devices=devices)
        super().__init__(cfg, n_nodes, replicas_per_node, mesh=mesh,
                         pipeline=pipeline)

    @property
    def n_shard_devices(self) -> int:
        return int(self.mesh.devices.size)


def default_fabric(cfg: FabricConfig = FabricConfig(),
                   n_nodes: int = 1,
                   replicas_per_node: int = 1,
                   pipeline: str = "batched") -> ArrayFabric:
    """The production entry point servers/adapters default to: mesh-placed
    TSU shards (``ShardedArrayFabric``) whenever the config's shards can
    actually spread over more than one device, the plain single-device
    ``ArrayFabric`` otherwise (including n_shards=1 configs on
    multi-device hosts — a 1-device mesh would pay the shard_map masked
    execution for zero placement benefit).

    Both run the batched grant pipeline by default: ONE packed grant
    collective per batch and the vectorized miss pass (DESIGN.md §9), so
    sharded placement no longer trades batch throughput for locality.
    ``pipeline="scan"`` selects the per-op schedule for ordering-sensitive
    debugging."""
    cfg = _bounded(cfg)
    if len(jax.devices()) > 1:
        from repro.launch.mesh import make_fabric_mesh
        mesh = make_fabric_mesh(n_shards=cfg.n_shards)
        if int(mesh.devices.size) > 1:
            return ShardedArrayFabric(cfg, n_nodes, replicas_per_node,
                                      mesh=mesh, pipeline=pipeline)
    return ArrayFabric(cfg, n_nodes, replicas_per_node, pipeline=pipeline)
