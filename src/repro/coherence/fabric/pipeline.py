"""The batched grant pipeline: vectorized miss / write / fence passes.

PR 3's two-phase batched read served every replica-tier lease hit with ONE
vectorized probe (phase 1) but re-ran the miss subset through the exact
per-op scan.  PR 5 completed the fast path (DESIGN.md §9): the whole miss
subset is served by a SECOND vectorized pass — one batched tier probe, one
batched TSU grant (``state.tsu_lease_batch``), one batched fill per tier —
and PR 6 added the posted-write twin.  This module now carries the ISSUE 8
tentpole: **graph-colored rounds** and a **lane-static write pass**, plus a
dedicated **fence pass**, so a set-colliding storm needs `max chain depth`
rounds instead of `number of contiguous conflict-free segments`.

Bit-identity with the sequential oracle (`HostFabric`, and the
``pipeline="scan"`` op-scan) is preserved by executing the pass over
**conflict-free rounds**:

  * ``conflict_rounds`` assigns each miss-subset op a round by
    order-preserving graph coloring: ops conflict when they share a key, a
    replica-tier set, or a shared-tier set, and within every such conflict
    chain round numbers strictly increase in op order (chain-depth
    first-fit, see ``color_rounds``).  Ops in one round touch disjoint
    cache state, hence executing them simultaneously equals executing
    them sequentially — and ops in *different* rounds that share state are
    executed in op order because their rounds are ordered.  The colored
    assignment never uses more rounds than the greedy contiguous splitter
    (``conflict_rounds_greedy``, kept as the property-test oracle).
  * The one piece of state every op shares — the per-store LRU tick — is
    reproduced exactly in two steps: inside the round scan each touch/fill
    writes a *provisional* tick (its execution-order rank, the §9
    prefix-sum math), and after the scan a permutation LUT remaps every
    provisional tick to the exact op-order value the sequential scan would
    have written.  Within any one set the events already execute in op
    order (same-set ops conflict, so they sit in ordered rounds), so every
    intermediate victim/probe decision is exact; only the absolute stored
    tick values need the final remap.  When rounds are contiguous the
    remap is the identity.

All rounds run inside ONE jitted ``lax.scan`` over the round masks (the
fabric state is the scan carry, so XLA updates it in place; per-op
results accumulate into one packed ``[7, M]`` buffer), and on the sharded
fabric the packed TSU buffer is assembled ONCE before the round scan —
the per-batch collective budget stays O(1) no matter how many rounds the
subset needs.

The write pass is **lane-static**: the bounded ring's drain schedule is a
pure function of op index (op j drains iff L0 + j + 1 > max_in_flight), so
``write_schedule`` resolves every drained entry on the host and hands the
pass a per-lane ``sched`` block — the ring scatter, head/len update and
LRU tick ranks all hoist out of the round scan, and the in-scan body keeps
only the state-dependent math (TSU commits, clock chains, tier installs,
counters).  ``make_fence_pass`` drains *all* node queues in node order with
the same machinery and ends with the §11b global-clock jump.

``make_miss_pass``/``make_write_pass``/``make_fence_pass`` return pure
passes; `arrays.py` owns jitting and the mesh placement (packed-TSU
``owner_gather`` in, ``owner_take`` out).  ``collective_counts`` walks a
jaxpr and reports how many collectives it contains and how many sit inside
a scan/while loop — the parity suite's O(1)-collectives-per-batch pin and
the ``batched_grants`` benchmark row both read it.
"""
from __future__ import annotations

import collections
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.coherence.fabric.stats import GI, G_KEYS, RI, R_KEYS
from repro.core import state as S
from repro.kernels.tier_pass import miss_round
# the packed per-op result block ([7, M] int32) — the layout contract now
# lives in core.state so the simulator's round step emits the same record
# (re-exported here for existing consumers)
from repro.core.state import RES_FIELDS  # noqa: F401

_i32 = jnp.int32
_NEG = jnp.int32(-2 ** 30)


def _b2i(b):
    return b.astype(_i32)


def _gsum(**kw):
    out = jnp.zeros((len(G_KEYS),), _i32)
    return out.at[jnp.array([GI[k] for k in kw], _i32)].add(
        jnp.stack(list(kw.values())))


def _rsum(**kw):
    out = jnp.zeros((len(R_KEYS),), _i32)
    return out.at[jnp.array([RI[k] for k in kw], _i32)].add(
        jnp.stack(list(kw.values())))


# ------------------------------------------------------------ round coloring
def color_rounds(footprints: Sequence[Sequence]) -> List[int]:
    """Order-preserving chain-depth graph coloring.

    ``footprints[j]`` is the set of resources op *j* touches; two ops
    conflict iff their footprints intersect.  The classic interval-free
    relaxation: op *j*'s color is one more than the largest color among
    the **last** prior user of each of its resources —

        color(j) = max(0, max_{res in fp(j)} last[res] + 1)

    which is valid because colors strictly increase along every resource
    chain (so the *last* user of a resource carries the maximum color of
    all its users, and no op in any round below the bound shares a
    resource with *j*), order-preserving within every conflict chain
    (conflicting ops get strictly increasing colors in op order), and
    never worse than the greedy contiguous splitter (by induction: every
    hard predecessor of *j* has a strictly smaller greedy round, so the
    bound never exceeds *j*'s greedy round).  O(n) over footprint sizes.
    """
    last: dict = {}
    colors: List[int] = []
    for fp in footprints:
        c = 0
        for res in fp:
            p = last.get(res)
            if p is not None and p + 1 > c:
                c = p + 1
        for res in fp:
            last[res] = c
        colors.append(c)
    return colors


def _colors_to_rounds(colors: Sequence[int]) -> List[np.ndarray]:
    n_rounds = (max(colors) + 1) if len(colors) else 1
    rounds: List[List[int]] = [[] for _ in range(n_rounds)]
    for j, c in enumerate(colors):
        rounds[c].append(j)
    return [np.asarray(r, np.int64) for r in rounds]


def conflict_rounds(kids, s1, s2) -> List[np.ndarray]:
    """Split a miss subset (op order) into conflict-free rounds by
    chain-depth graph coloring: within a round all keys, replica sets and
    shared sets are distinct, and any two ops that share one of those
    resources land in rounds ordered like the ops — so committing the
    rounds in order IS the sequential op order for every conflict chain.
    Returns index arrays into the subset (ascending within each round);
    concatenated they are a permutation of ``range(len(kids))``.  Never
    more rounds than ``conflict_rounds_greedy``."""
    fps = [((0, k), (1, a), (2, b))
           for k, a, b in zip(np.asarray(kids).tolist(),
                              np.asarray(s1).tolist(),
                              np.asarray(s2).tolist())]
    return _colors_to_rounds(color_rounds(fps))


def conflict_rounds_greedy(kids, s1, s2) -> List[np.ndarray]:
    """The PR-5 splitter (kept as the coloring property-test oracle):
    maximal contiguous conflict-free segments in op order."""
    rounds: List[np.ndarray] = []
    cur: List[int] = []
    seen_k, seen_1, seen_2 = set(), set(), set()
    for i, (k, a, b) in enumerate(zip(np.asarray(kids).tolist(),
                                      np.asarray(s1).tolist(),
                                      np.asarray(s2).tolist())):
        if k in seen_k or a in seen_1 or b in seen_2:
            rounds.append(np.asarray(cur, np.int64))
            cur = []
            seen_k, seen_1, seen_2 = set(), set(), set()
        cur.append(i)
        seen_k.add(k)
        seen_1.add(a)
        seen_2.add(b)
    rounds.append(np.asarray(cur, np.int64))
    return rounds


def round_masks(rounds: List[np.ndarray], n_rounds: int,
                width: int) -> np.ndarray:
    """Pack conflict rounds into a dense ``[n_rounds, width]`` bool mask
    matrix (rows beyond ``len(rounds)`` are empty — a fully masked pass is
    a no-op), the shape the one-jit round scan consumes."""
    masks = np.zeros((n_rounds, width), bool)
    for r, idxs in enumerate(rounds):
        masks[r, idxs] = True
    return masks


def make_miss_pass(W1: int, W2: int, KS: int):
    """Build the vectorized miss pass for one tier geometry (W1/W2 = tier
    way counts, i.e. the trash-way indices; KS = TSU shard count).

    The returned function has the signature
    ``pass_(af, ops, masks, rep, node, rd, wr) -> (af, res)`` where ``af``
    is the fabric state pytree (arrays._AF), ``ops`` is the packed
    ``[4, M]`` int32 op block (rows: kid, replica set, shared set, TSU
    shard; padded lanes are all-zero and masked out), ``masks`` is the
    [R, M] conflict-round matrix (each row one conflict-free round, from
    ``conflict_rounds``), rep/node are scalars (one replica per
    read_batch call), and ``res`` is the packed [7, M] per-op result
    block (``RES_FIELDS`` order) of the op-scan's read path.

    The rounds run as ONE ``lax.scan`` with the fabric state as carry;
    each round body is the read path of ``arrays._build_run``'s step
    function re-expressed over a whole conflict-free round at once —
    every lease decision is the same ``core.state`` call the scan makes.
    Under graph-colored rounds the in-scan LRU ticks are provisional
    (execution-order ranks); the scan carries each lane's touch/fill
    flags and a post-scan permutation LUT remaps every provisional tick
    to the exact op-order value (identity for contiguous rounds) — see
    the module docstring and DESIGN.md §12b.
    """
    i32 = jnp.int32
    b2i = _b2i

    def round_body(af, out, act, kids, s1, s2, shard, rep, node, rd, wr):
        M = kids.shape[0]
        reps = jnp.full((M,), rep, i32)
        nodes = jnp.full((M,), node, i32)
        zt = jnp.zeros_like(shard)

        # ---- fused per-lane round math (kernels.tier_pass.miss_round):
        # replica probe, shared probe, Algorithm 3 TSU read grant and
        # both install levels in ONE Pallas grid pass — the same
        # ``core.state``/``core.protocol`` rules the op-scan applies,
        # per DESIGN.md §12c.  Only the cross-lane state scatters
        # (self-invalidation, LRU touch/fill, TSU commit) stay outside.
        (th1, h1, way1, th2, h2, way2, fndF, tway, mwts, mrts, nmem, ovf,
         nwA, nrA, nw1, nr1) = miss_round(
            af.rp.tag[reps, s1][..., :-1], af.rp.rts[reps, s1][..., :-1],
            af.sh.tag[nodes, s2][..., :-1], af.sh.rts[nodes, s2][..., :-1],
            af.sh.wts[nodes, s2][..., :-1],
            af.tsu.tag[shard, zt][..., :-1],
            af.tsu.memts[shard, zt][..., :-1],
            af.rp.cts[reps], af.sh.cts[nodes], kids, b2i(act),
            jnp.broadcast_to(jnp.asarray(rd, i32), (M,)))

        # ---- replica classification + self-invalidate (ReplicaCache.get)
        hit_ver = af.rp.ver[reps, s1, way1]
        hit_gs = af.rp_gseq[reps, s1, way1]
        miss = act & ~h1
        coh = miss & th1
        comp = miss & ~th1
        w1d = jnp.where(coh, way1, W1)
        rp_tag = af.rp.tag.at[reps, s1, w1d].set(
            jnp.where(coh, S.INVALID, af.rp.tag[reps, s1, w1d]))

        # ---- shared self-invalidate (SharedCache.get, on a replica miss)
        sh_ver = af.sh.ver[nodes, s2, way2]
        sh_gs = af.sh_gseq[nodes, s2, way2]
        coh2 = th2 & ~h2
        w2d = jnp.where(coh2, way2, W2)
        sh_tag = af.sh.tag.at[nodes, s2, w2d].set(
            jnp.where(coh2, S.INVALID, af.sh.tag[nodes, s2, w2d]))

        # ---- commit the round's TSU grants (state rules) + metadata
        need_mm = miss & ~h2
        tsu2 = S.tsu_commit_batch(af.tsu, shard, zt, tway, kids, nmem,
                                  fndF)
        mver = jnp.where(fndF, af.tsu_ver[shard, zt, tway], -1)
        mgs = jnp.where(fndF, af.tsu_gseq[shard, zt, tway], -1)
        home_miss = shard != node % KS

        # ---- response chain (what travels up to each tier)
        resp_found = h2 | fndF
        resp_ver = jnp.where(h2, sh_ver, mver)
        resp_gs = jnp.where(h2, sh_gs, mgs)

        # ---- provisional tick math (execution-order ranks): per op the
        # touch bump precedes the install bump, so op i's touch writes
        # tick0 + c[i] - fill[i] and its install tick0 + c[i] with
        # c = cumsum(touch + fill) — prefix sums over lane (= execution)
        # order.  Relative order within any one set equals op order (the
        # coloring invariant), so probes/victims are exact; the post-scan
        # LUT rewrites the absolute values to op-order ranks.
        c1 = jnp.cumsum(b2i(th1) + b2i(resp_found))
        lru_t1 = af.rp_tick[rep] + c1 - b2i(resp_found)
        lru_f1 = af.rp_tick[rep] + c1
        c2 = jnp.cumsum(b2i(th2) + b2i(fndF))
        lru_t2 = af.sh_tick[node] + c2 - b2i(fndF)
        lru_f2 = af.sh_tick[node] + c2

        def tier_fill(tag, lru, arrays, idx, st, th, touch_lru, way,
                      fill_c, vals, fill_lru, trash):
            """Touch + victim + fill on one (already-dropped) tier: the
            LRU touch refresh, then the packed install at the victim way
            — direct per-field scatters so the round scan updates the
            carried arrays in place."""
            wt = jnp.where(th, way, trash)
            lru = lru.at[idx, st, wt].set(
                jnp.where(th, touch_lru, lru[idx, st, wt]))
            vic = S.victim(tag, lru, idx, st)
            evicted = fill_c & (tag[idx, st, vic] != S.INVALID)
            wf = jnp.where(fill_c, vic, trash)

            def put(a, v):
                return a.at[idx, st, wf].set(
                    jnp.where(fill_c, v, a[idx, st, wf]))

            outs = [put(a, v) for a, v in arrays]
            return put(tag, vals), put(lru, fill_lru), outs, evicted

        sh_tag2, sh_lru2, (sh_wts2, sh_rts2, sh_ver2, sh_gseq2), evF = \
            tier_fill(sh_tag, af.sh.lru,
                      [(af.sh.wts, nwA), (af.sh.rts, nrA),
                       (af.sh.ver, mver), (af.sh_gseq, mgs)],
                      nodes, s2, th2, lru_t2, way2, fndF, kids, lru_f2, W2)
        rp_tag2, rp_lru2, (rp_wts2, rp_rts2, rp_ver2, rp_gseq2), ev1 = \
            tier_fill(rp_tag, af.rp.lru,
                      [(af.rp.wts, nw1), (af.rp.rts, nr1),
                       (af.rp.ver, resp_ver), (af.rp_gseq, resp_gs)],
                      reps, s1, th1, lru_t1, way1, resp_found, kids,
                      lru_f1, W1)

        # ---- counters: the scan's per-read gv/rv calls, summed per round
        n = lambda b: jnp.sum(b2i(b))
        b12, b2m, big = S.link_bytes(n(miss), n(need_mm),
                                     n(need_mm & home_miss))
        g2 = af.g + _gsum(
            reads=n(act), l1_hits=n(h1), l2_hits=n(h2), l1_to_l2=n(miss),
            coh_miss_l1=n(coh), coh_miss_l2=n(coh2),
            self_invalidations=n(coh) + n(coh2), compulsory=n(comp),
            l2_to_mm=n(need_mm), pcie_blocks=n(need_mm & home_miss),
            refetches=n(resp_found), overflow_reinits=n(ovf),
            capacity_evictions=n(evF) + n(ev1),
            bytes_l1_l2=b12, bytes_l2_mm=b2m, bytes_inter_gpu=big)
        r2 = af.r.at[rep].add(_rsum(
            reads=n(act), l1_hits=n(h1), l2_hits=n(h2), l1_to_l2=n(miss),
            coh_miss_l1=n(coh), coh_miss_l2=n(coh2),
            self_invalidations=n(coh) + n(coh2), compulsory=n(comp),
            refetches=n(resp_found),
            capacity_evictions=n(evF) + n(ev1)))

        af = af._replace(
            rp=af.rp._replace(tag=rp_tag2, wts=rp_wts2, rts=rp_rts2,
                              ver=rp_ver2, lru=rp_lru2),
            rp_gseq=rp_gseq2,
            rp_tick=af.rp_tick.at[rep].add(
                jnp.sum(b2i(th1) + b2i(resp_found))),
            sh=af.sh._replace(tag=sh_tag2, wts=sh_wts2, rts=sh_rts2,
                              ver=sh_ver2, lru=sh_lru2),
            sh_gseq=sh_gseq2,
            sh_tick=af.sh_tick.at[node].add(jnp.sum(b2i(th2) + b2i(fndF))),
            tsu=tsu2, g=g2, r=r2)

        vals = jnp.stack([
            b2i(h1 | resp_found),
            jnp.where(h1, hit_ver, jnp.where(resp_found, resp_ver, -1)),
            jnp.where(h1, hit_gs, jnp.where(resp_found, resp_gs, -1)),
            jnp.where(h1, 0, jnp.where(h2, 1, jnp.where(fndF, 2, 3))),
            jnp.where(fndF, mwts, 0), jnp.where(fndF, mrts, 0),
            b2i(fndF)])                               # RES_FIELDS order
        return (af, jnp.where(act[None, :], vals, out),
                th1, resp_found, th2, fndF)

    def pass_(af, ops, masks, rep, node, rd, wr):
        kids, s1, s2, shard = ops[0], ops[1], ops[2], ops[3]
        M = kids.shape[0]
        out0 = jnp.zeros((len(RES_FIELDS), M), i32)
        z0 = jnp.zeros((M,), i32)
        t0_rp = af.rp_tick[rep]
        t0_sh = af.sh_tick[node]

        def step(carry, act):
            af, out, fT1, fF1, fT2, fF2 = carry
            af, out, th1, rf, th2, ff = round_body(
                af, out, act, kids, s1, s2, shard, rep, node, rd, wr)
            return (af, out, fT1 + b2i(th1), fF1 + b2i(rf),
                    fT2 + b2i(th2), fF2 + b2i(ff)), None

        (af, out, fT1, fF1, fT2, fF2), _ = jax.lax.scan(
            step, (af, out0, z0, z0, z0, z0), masks)

        # ---- exact-LRU remap (DESIGN.md §12b): every provisional tick is
        # t0 + (execution-order rank of its event); the LUT sends that
        # rank to t0 + (op-order rank).  Events are lane-major pairs
        # (touch, fill) — the op-order event sequence — and each lane sits
        # in exactly one round, so the provisional rank decomposes into
        # `events in earlier rounds` + `in-round lane-prefix rank`.
        mi = masks.astype(i32)
        rnd = jnp.argmax(mi, axis=0)              # [M] round of each lane
        lane2 = jnp.repeat(rnd, 2)
        pos2 = jnp.arange(2 * M)

        def remap(row, f_touch, f_fill, t0):
            fl = jnp.stack([f_touch, f_fill], axis=1).reshape(-1)   # [2M]
            exact = jnp.cumsum(fl)                # op-order rank (1-based)
            per_round = mi @ (f_touch + f_fill)
            base = jnp.cumsum(per_round) - per_round
            inround = jnp.cumsum(jnp.repeat(mi, 2, axis=1) * fl[None, :],
                                 axis=1)
            prov = base[lane2] + inround[lane2, pos2]
            idx = jnp.where(fl > 0, prov, 2 * M + 1)
            lut = jnp.zeros((2 * M + 2,), i32).at[idx].set(
                jnp.where(fl > 0, t0 + exact, 0))
            d = row - t0                          # >0 iff written this pass
            return jnp.where(d > 0, lut[jnp.clip(d, 0, 2 * M + 1)], row)

        af = af._replace(
            rp=af.rp._replace(lru=af.rp.lru.at[rep].set(
                remap(af.rp.lru[rep], fT1, fF1, t0_rp))),
            sh=af.sh._replace(lru=af.sh.lru.at[node].set(
                remap(af.sh.lru[node], fT2, fF2, t0_sh))))
        return af, out

    return pass_


# ------------------------------------------------------ batched write pass
# The packed per-op result block of the write pass ([6, M] int32): each op
# is a posted write, so the only externally visible output is its drain —
# dcount (0/1) plus the drained grant's key/version/lease/gseq, exactly the
# op-scan's dlog_* record restricted to the one-drain-per-write case.
WRITE_RES_FIELDS = ("dcount", "dlog_key", "dlog_ver", "dlog_wts",
                    "dlog_rts", "dlog_gseq")

# the per-lane drain schedule block handed to the write pass ([7, M] int32)
WRITE_SCHED_FIELDS = ("drain", "dkey", "drep", "dwl", "dshard", "ds1",
                      "ds2")


def write_schedule(kids, s1, s2, shard, rep, wl, pending, maxif,
                   splitter: str = "colored"):
    """Resolve a write batch's drain schedule and split it into
    conflict-free rounds for the lane-static batched write pass.

    The bounded ring's drain schedule is **static in op index**: with L0
    pending entries at batch start, op j (0-based) drains the queue head
    iff ``L0 + j + 1 > maxif`` — so this host-side simulation resolves
    every drained entry exactly, independent of round assignment.

    ``pending`` is the node's queue at batch start, oldest first, as
    ``(kid, s1, s2, shard, rep, wl)`` tuples (``wl`` = the write-lease
    override recorded when the entry was posted, -1 for the default);
    ``rep``/``wl`` describe this batch's pushes.  Returns ``(rounds,
    sched)`` where ``sched`` is the ``[7, n]`` int32
    ``WRITE_SCHED_FIELDS`` block (zeros on non-drain lanes) and
    ``rounds`` are index arrays into the batch (a permutation of
    ``range(n)`` when concatenated; ascending within each round).

    Round constraints (op footprints): a push claims its key and its
    ``(rep, s1)`` replica set; a drain claims the drained entry's TSU
    shard and ``(node, s2)`` shared set always, plus its key and
    ``(drep, s1)`` replica set unless the entry was pushed in the very
    round the drain lands in (the pass applies every pending install
    before any drain install, so a same-round drain re-probes the
    pending line exactly as the sequential scan would).  The ``colored``
    splitter is chain-depth coloring (see ``color_rounds``) with three
    *order* side constraints that keep the pass's running-maximum clock
    chains and the TSU allocation sequencer exact (DESIGN.md §12b):

      * a drain never lands in an earlier round than any prior drain
        (drains execute in op order globally — gseq ranks, the node
        clock chain and the per-replica clock chains then read in lane
        order = op order);
      * a push never lands in an earlier round than a prior drain whose
        entry belongs to the push's replica (the pending line's
        ``pend_cts`` must see that drain's replica-clock bump);
      * a drain of this replica's own entry never lands in an earlier
        round than any prior push (the prior pushes' ``pend_cts`` must
        NOT see this drain's bump; ties resolve in-round by exclusive
        prefix maxima).

    ``splitter="greedy"`` reproduces the PR-6 contiguous splitter (the
    property-test oracle; colored never uses more rounds)."""
    kids = np.asarray(kids).tolist()
    s1 = np.asarray(s1).tolist()
    s2 = np.asarray(s2).tolist()
    shard = np.asarray(shard).tolist()
    n = len(kids)
    wl = int(wl)

    # ---- static drain schedule: simulate the bounded ring on the host
    q = collections.deque((tuple(e), -1) for e in pending)
    drain = np.zeros((n,), np.int64)
    dent: List = [None] * n        # drained entry per op
    dpe: List = [None] * n         # in-batch push op of the drained entry
    for j in range(n):
        q.append(((kids[j], s1[j], s2[j], shard[j], rep, wl), j))
        if len(q) > maxif:
            e, pe = q.popleft()
            drain[j] = 1
            dent[j] = e
            dpe[j] = pe if pe >= 0 else None

    sched = np.zeros((len(WRITE_SCHED_FIELDS), n), np.int32)
    sched[0] = drain
    for j in range(n):
        if drain[j]:
            ek, e1, e2, esh, erep, ewl = dent[j]
            sched[1, j] = ek
            sched[2, j] = erep
            sched[3, j] = ewl
            sched[4, j] = esh
            sched[5, j] = e1
            sched[6, j] = e2

    if splitter == "greedy":
        colors = _write_colors_greedy(n, kids, s1, rep, drain, dent, dpe)
    else:
        colors = _write_colors_chain(n, kids, s1, rep, drain, dent, dpe)
    return _colors_to_rounds(colors) if n else [np.asarray([], np.int64)], \
        sched


def _write_colors_greedy(n, kids, s1, rep, drain, dent, dpe):
    """The PR-6 contiguous splitter, re-expressed over the static drain
    schedule: break before op j whenever its footprint intersects the
    open round's, with the same-round-push exemption re-evaluated after a
    break (the pushed entry may now sit in the previous round)."""
    colors: List[int] = []
    r = 0
    seen_k, seen_1, seen_2, seen_sh = set(), set(), set(), set()
    for j in range(n):
        def fp(r_):
            fk, f1, f2, fsh = {kids[j]}, {(rep, s1[j])}, set(), set()
            if drain[j]:
                ek, e1, e2, esh, erep, _ = dent[j]
                fsh.add(esh)
                f2.add(e2)
                pe = dpe[j]
                same_round = pe is not None and (pe == j or
                                                 colors[pe] == r_)
                if not same_round:
                    fk.add(ek)
                    f1.add((erep, e1))
            return fk, f1, f2, fsh

        fk, f1, f2, fsh = fp(r)
        if (fk & seen_k) or (f1 & seen_1) or (f2 & seen_2) \
                or (fsh & seen_sh):
            r += 1
            seen_k, seen_1, seen_2, seen_sh = set(), set(), set(), set()
            fk, f1, f2, fsh = fp(r)
        colors.append(r)
        seen_k |= fk
        seen_1 |= f1
        seen_2 |= f2
        seen_sh |= fsh
    return colors


def _write_colors_chain(n, kids, s1, rep, drain, dent, dpe):
    """Chain-depth coloring for the write storm (see ``write_schedule``
    docstring for the constraint system).  Hard resources take
    ``last[res] + 1``; the three order side constraints are soft (ties
    allowed).  A drain of an entry pushed in this batch at op ``pe`` is
    *exempt* from its key/replica-set resources only when it can land
    exactly in ``colors[pe]`` (the push's round, where the pass's
    pending-before-drain install order reproduces the sequential
    push-then-drain); otherwise the key conflict forces it at least one
    round later."""
    last: dict = {}
    colors: List[int] = []
    max_dc = -1                  # max color of any drain so far
    max_dc_rep: dict = {}        # ... of drains per drained-entry replica
    max_push = -1                # max color of any op (= push) so far
    for j in range(n):
        push_res = ((0, kids[j]), (1, rep, s1[j]))
        lb = max(0, max_dc_rep.get(rep, -1))
        for res in push_res:
            p = last.get(res)
            if p is not None and p + 1 > lb:
                lb = p + 1
        if not drain[j]:
            for res in push_res:
                last[res] = lb
            colors.append(lb)
            if lb > max_push:
                max_push = lb
            continue

        ek, e1, e2, esh, erep, _ = dent[j]
        d0_res = ((3, esh), (2, e2))
        dk_res = ((0, ek), (1, erep, e1))
        lb_ex = max(lb, max_dc)
        if erep == rep and max_push > lb_ex:
            lb_ex = max_push
        for res in d0_res:
            p = last.get(res)
            if p is not None and p + 1 > lb_ex:
                lb_ex = p + 1
        pe = dpe[j]
        if pe is not None and (pe == j or lb_ex <= colors[pe]):
            c = lb_ex if pe == j else colors[pe]
        else:
            c = lb_ex
            for res in dk_res:
                p = last.get(res)
                if p is not None and p + 1 > c:
                    c = p + 1
        for res in push_res + d0_res + dk_res:
            last[res] = c
        if c > max_dc:
            max_dc = c
        if c > max_dc_rep.get(erep, -1):
            max_dc_rep[erep] = c
        if c > max_push:
            max_push = c
        colors.append(c)
    return colors


def write_rounds_greedy(kids, s1, s2, shard, rep, wl, pending, maxif):
    """Greedy contiguous write rounds (the coloring property-test
    oracle) — ``write_schedule`` with ``splitter="greedy"``."""
    return write_schedule(kids, s1, s2, shard, rep, wl, pending, maxif,
                          splitter="greedy")


def _tier_install(tier, gseq_a, idx, st, key, wts, rts, ver, gs, lru_v,
                  th, way, active, trash):
    """Vectorized ``install_at``: in place on ``(th, way)``, else the
    victim way; LRU values are the caller's prefix-sum ranks.  The round
    contract guarantees all active ``(idx, st)`` sets are distinct, so
    the scatters commute with the sequential order."""
    vic = S.victim(tier.tag, tier.lru, idx, st)
    w0 = jnp.where(th, way, vic)
    evicted = active & ~th & (tier.tag[idx, st, w0] != S.INVALID)
    w = jnp.where(active, w0, trash)

    def pt(a, v):
        return a.at[idx, st, w].set(jnp.where(active, v, a[idx, st, w]))

    tier2 = tier._replace(tag=pt(tier.tag, key), wts=pt(tier.wts, wts),
                          rts=pt(tier.rts, rts), ver=pt(tier.ver, ver),
                          lru=pt(tier.lru, lru_v))
    return tier2, pt(gseq_a, gs), evicted


def make_write_pass(W1: int, W2: int, KS: int, NN: int, NR: int, Q: int,
                    MAXIF: int):
    """Build the lane-static vectorized write pass for one fabric
    geometry (W1/W2 = tier trash-way indices, KS = TSU shard count,
    NN/NR = node/replica counts, Q = ring capacity, MAXIF = max in-flight
    writes).

    The returned function has the signature
    ``pass_(af, ops, sched, masks, rep, node, wl, rd, wr) -> (af, res)``:
    ``ops`` is the packed [4, M] int32 op block (kid, s1, s2, shard),
    ``sched`` the [7, M] ``WRITE_SCHED_FIELDS`` drain-schedule block from
    ``write_schedule`` (every drained entry pre-resolved on the host —
    the ring is static in op index), ``masks`` the [R, M] round matrix,
    rep/node/wl scalars (one replica, one uniform write-lease override
    per ``write_batch`` call), and ``res`` the packed [6, M]
    ``WRITE_RES_FIELDS`` block.

    Everything round-independent hoists OUT of the round scan:

      * the real ring update — a single keep-last scatter at op-order
        slots ``(H0 + L0 + rank - 1) mod Q`` (two pushes collide mod Q
        only when exactly Q pushes apart, and the earlier one is
        provably drained before the later lands: the queue never holds
        Q entries since MAXIF + 1 <= Q - 1), with head/len advanced once
        by the batch totals;
      * the LRU tick ranks — 2-D prefix sums over per-replica increments
        from the batch-start ticks (op j's pending install writes its
        submitter rank minus its own drain's contribution; the drain
        install writes the drained replica's rank; the shared tier
        counts drains), with the tick counters advanced once.

    The round scan keeps only the state-dependent math, exactly the
    op-scan's write path over a whole conflict-free round at once:

      * ONE batched TSU commit per round (``state.tsu_commit_write_batch``
        — the round contract guarantees distinct keys and at most one
        write per shard);
      * clocks via running maxima (DESIGN.md §9c prefix-sum style): the
        TSU grant is clock-independent, so the node clock after drain i
        is ``max(cts0, cummax(mwts)_i)`` and each replica clock chains
        the same way over its own drains — closed forms of the
        sequential ``install``/``cts_after_write`` recurrences; the
        scheduler's order side constraints make lane order within and
        across rounds equal drain op order, so the chains stay exact
        under coloring;
      * pending installs (store-buffer lines) against the pre-round
        replica state, then the drain installs — whose probes run AFTER
        the pending scatters so a drain of a same-round push sees its
        pending line, exactly as the scan does.

    All rounds run inside ONE ``lax.scan``; on the sharded fabric the
    caller brackets the pass with the gather/scatter exchange
    (``arrays._xin``/``_xout``) so the full TSU table is assembled with
    ONE collective per batch.
    """
    i32 = jnp.int32
    b2i = _b2i
    NEG = _NEG

    def round_body(af, out, act, kids, s1, drain_l, dkey, drep, dwl,
                   dshard, ds1, ds2, lru_pend, lru_drain, lru_sh, rep,
                   node, rd, wr):
        M = kids.shape[0]
        iota = jnp.arange(M, dtype=i32)
        reps = jnp.full((M,), rep, i32)
        nodes = jnp.full((M,), node, i32)
        dr = act & drain_l

        # ---- ONE batched TSU write for the round's drains (state rules)
        dwl_eff = jnp.where(dwl >= 0, dwl, wr)
        (mwts, mrts, dver, gs, evict, ovf, tsu2, ver2, gseq2, seq2, nseq2,
         gnext2) = S.tsu_commit_write_batch(
            af.tsu, af.tsu_ver, af.tsu_gseq, af.tsu_seq, af.tsu_nseq,
            af.gseq_next, dshard, dkey, dwl_eff, rd, dr)

        # ---- clock chains: running maxima reproduce the sequential
        # install/cts_after_write recurrences (grants are clock-free)
        cts0n = af.sh.cts[node]
        run_mw = jax.lax.cummax(jnp.where(dr, mwts, NEG))
        nwA = jnp.maximum(cts0n, run_mw)
        nrA = jnp.maximum(nwA + 1, mrts)
        onehot_d = (jnp.arange(NR, dtype=i32)[:, None] == drep[None, :]) \
            & dr[None, :]
        runsA = jax.lax.cummax(jnp.where(onehot_d, nwA[None, :], NEG),
                               axis=1)
        cts0r = af.rp.cts
        nwB = jnp.maximum(cts0r[drep], runsA[drep, iota])
        nrB = jnp.maximum(nwB + 1, nrA)
        exclA = jnp.concatenate([jnp.full((NR, 1), NEG), runsA[:, :-1]],
                                axis=1)
        pend_cts = jnp.maximum(cts0r[rep], exclA[rep])

        # ---- pending installs (store-buffer lines: wts=rts=cts, ver=-1)
        # against the pre-round replica state, then the drain installs —
        # whose probes run AFTER the pending scatters so a drain of a
        # same-round push sees its pending line, exactly as the scan does
        negs = jnp.full((M,), -1, i32)
        thP, wayP = S.probe(af.rp.tag, reps, s1, kids)
        rpA, rpgA, evP = _tier_install(
            af.rp, af.rp_gseq, reps, s1, kids, pend_cts, pend_cts, negs,
            negs, lru_pend, thP & act, wayP, act, W1)
        thA, wayA = S.probe(af.sh.tag, nodes, ds2, dkey)
        sh2, shg2, ev1 = _tier_install(
            af.sh, af.sh_gseq, nodes, ds2, dkey, nwA, nrA, dver, gs,
            lru_sh, thA & dr, wayA, dr, W2)
        thB, wayB = S.probe(rpA.tag, drep, ds1, dkey)
        rp2, rpg2, ev2 = _tier_install(
            rpA, rpgA, drep, ds1, dkey, nwB, nrB, dver, gs, lru_drain,
            thB & dr, wayB, dr, W1)

        # ---- counters: the scan's per-write gv/rv calls, summed
        n = lambda b: jnp.sum(b2i(b))
        Pn = n(act)
        D = n(dr)
        cross = dr & (dshard != node % KS)
        b12, b2m, big = S.link_bytes(Pn, D, n(cross))
        g2 = af.g + _gsum(
            writes=Pn, l1_to_l2=Pn, l2_to_mm=D, write_throughs=D,
            pcie_blocks=n(cross), tsu_evictions=n(evict),
            overflow_reinits=n(ovf),
            capacity_evictions=n(evP) + n(ev1) + n(ev2),
            bytes_l1_l2=b12, bytes_l2_mm=b2m, bytes_inter_gpu=big)
        r2 = af.r.at[rep].add(_rsum(
            writes=Pn, l1_to_l2=Pn, capacity_evictions=n(evP)))
        r2 = r2.at[drep, RI["write_throughs"]].add(b2i(dr))
        r2 = r2.at[drep, RI["capacity_evictions"]].add(b2i(ev2))

        af = af._replace(
            rp=rp2._replace(cts=jnp.maximum(cts0r, runsA[:, -1])),
            rp_gseq=rpg2,
            sh=sh2._replace(cts=af.sh.cts.at[node].set(
                jnp.maximum(cts0n, run_mw[-1]))),
            sh_gseq=shg2,
            tsu=tsu2, tsu_ver=ver2, tsu_gseq=gseq2, tsu_seq=seq2,
            tsu_nseq=nseq2, gseq_next=gnext2, g=g2, r=r2)

        vals = jnp.stack([
            b2i(dr), jnp.where(dr, dkey, -1),
            jnp.where(dr, dver, -1), jnp.where(dr, mwts, -1),
            jnp.where(dr, mrts, -1), jnp.where(dr, gs, -1),
        ])                                       # WRITE_RES_FIELDS order
        return af, jnp.where(act[None, :], vals, out)

    def pass_(af, ops, sched, masks, rep, node, wl, rd, wr):
        kids, s1, s2, shard = ops[0], ops[1], ops[2], ops[3]
        drain_l = sched[0].astype(bool)
        dkey = sched[1]
        drep = jnp.clip(sched[2], 0, NR - 1)
        dwl = sched[3]
        dshard = sched[4]
        ds1 = sched[5]
        ds2 = sched[6]
        M = kids.shape[0]
        iota = jnp.arange(M, dtype=i32)
        act_any = jnp.any(masks, axis=0)
        dr_any = act_any & drain_l

        # ---- real ring update (lane-static): keep-last scatter at
        # op-order slots, head/len advanced once by the batch totals
        prank = jnp.cumsum(b2i(act_any))
        Pt = prank[-1]
        Dt = jnp.sum(b2i(dr_any))
        L0 = af.wq_len[node]
        H0 = af.wq_head[node]
        push_v = {"key": kids, "rep": jnp.full((M,), rep, i32),
                  "wl": jnp.full((M,), wl, i32), "shard": shard,
                  "set1": s1, "set2": s2}
        keep = act_any & (prank + Q > Pt)
        slot = (H0 + L0 + prank - 1) % Q
        nrow = jnp.where(keep, node, NN)        # OOB row -> dropped
        wq2 = {f: a.at[nrow, slot].set(push_v[f], mode="drop")
               for f, a in af.wq.items()}

        # ---- LRU tick ranks (lane-static): §9c prefix sums over
        # per-replica increments from the batch-start ticks
        onehot_d = (jnp.arange(NR, dtype=i32)[:, None] == drep[None, :]) \
            & dr_any[None, :]
        inc = b2i(act_any)[None, :] * b2i(
            jnp.arange(NR, dtype=i32)[:, None] == rep) + b2i(onehot_d)
        c = jnp.cumsum(inc, axis=1)
        tick0 = af.rp_tick
        lru_pend = tick0[rep] + c[rep] - b2i(dr_any & (drep == rep))
        lru_drain = tick0[drep] + c[drep, iota]
        lru_sh = af.sh_tick[node] + jnp.cumsum(b2i(dr_any))

        af = af._replace(
            rp_tick=tick0 + c[:, -1],
            sh_tick=af.sh_tick.at[node].add(Dt),
            wq=wq2, wq_head=af.wq_head.at[node].set((H0 + Dt) % Q),
            wq_len=af.wq_len.at[node].add(Pt - Dt))

        out0 = jnp.zeros((len(WRITE_RES_FIELDS), M), i32)

        def step(carry, act):
            af, out = carry
            return round_body(af, out, act, kids, s1, drain_l, dkey,
                              drep, dwl, dshard, ds1, ds2, lru_pend,
                              lru_drain, lru_sh, rep, node, rd, wr), None

        (af, out), _ = jax.lax.scan(step, (af, out0), masks)
        return af, out

    return pass_


# ------------------------------------------------------------- fence pass
# the per-lane fence schedule block ([8, D] int32): one lane per queued
# posted write, in node order then FIFO order — the exact host drain order
FENCE_SCHED_FIELDS = ("ent", "dkey", "drep", "dwl", "dshard", "ds1",
                      "ds2", "dnode")


def fence_schedule(entries) -> Tuple[List[np.ndarray], np.ndarray]:
    """Build the fence drain schedule: ``entries`` is every node's queue
    concatenated in node order (each oldest-first), as
    ``(kid, s1, s2, shard, rep, wl, node)`` tuples.  Returns ``(rounds,
    sched)`` with ``sched`` the [8, n] ``FENCE_SCHED_FIELDS`` block.

    Rounds are greedy contiguous segments over the drain footprint (key,
    replica set, shared set, TSU shard): a fence drains in strict host
    order, and the drain-order side constraint (every drain >= all prior
    drains) collapses chain-depth coloring to exactly this contiguous
    segmentation — so the greedy split is the colored split here."""
    n = len(entries)
    sched = np.zeros((len(FENCE_SCHED_FIELDS), n), np.int32)
    rounds: List[np.ndarray] = []
    cur: List[int] = []
    seen: set = set()
    for j, (k, a, b, sh, rep, wl, node) in enumerate(entries):
        sched[:, j] = (1, k, rep, wl, sh, a, b, node)
        fp = {(0, k), (1, rep, a), (2, node, b), (3, sh)}
        if fp & seen:
            rounds.append(np.asarray(cur, np.int64))
            cur = []
            seen = set()
        cur.append(j)
        seen |= fp
    rounds.append(np.asarray(cur, np.int64))
    return rounds, sched


def make_fence_pass(W1: int, W2: int, KS: int, NN: int, NR: int, Q: int):
    """Build the vectorized fence pass: drain EVERY node's posted-write
    queue (node order, FIFO within a node), then jump every client clock
    to the global maximum — the op-scan's ``_fence`` handler (DESIGN.md
    §11b) over conflict-free rounds.

    The returned function has the signature
    ``pass_(af, sched, masks, rd, wr) -> (af, res, gmax)``: ``sched`` is
    the [8, D] ``FENCE_SCHED_FIELDS`` block from ``fence_schedule``
    (padded lanes have ``ent == 0``), ``masks`` the [R, D] round matrix,
    and ``res`` the packed [6, D] ``WRITE_RES_FIELDS`` block (one drain
    record per lane).  A fence is drains-only — no pending installs —
    so each round is the write pass's drain half generalized to
    multi-node lanes: per-node clock chains via per-node running maxima,
    per-replica chains as before (lanes are host-ordered and the
    schedule is contiguous, so lane order IS drain order everywhere).
    The ring bookkeeping, LRU ranks and tick advances are lane-static
    and hoist out of the scan; after the scan every ``cts`` jumps to the
    global max — the §11b barrier that makes all prior writes globally
    visible."""
    i32 = jnp.int32
    b2i = _b2i
    NEG = _NEG

    def round_body(af, out, act, ent_l, dkey, drep, dwl, dshard, ds1,
                   ds2, dnode, lru_rp, lru_sh, rd, wr):
        D = dkey.shape[0]
        iota = jnp.arange(D, dtype=i32)
        dr = act & ent_l

        dwl_eff = jnp.where(dwl >= 0, dwl, wr)
        (mwts, mrts, dver, gs, evict, ovf, tsu2, ver2, gseq2, seq2, nseq2,
         gnext2) = S.tsu_commit_write_batch(
            af.tsu, af.tsu_ver, af.tsu_gseq, af.tsu_seq, af.tsu_nseq,
            af.gseq_next, dshard, dkey, dwl_eff, rd, dr)

        # ---- clock chains, generalized per node: each node's clock
        # chains over its own drains (lane order = host drain order)
        onehot_n = (jnp.arange(NN, dtype=i32)[:, None] == dnode[None, :]) \
            & dr[None, :]
        runsN = jax.lax.cummax(jnp.where(onehot_n, mwts[None, :], NEG),
                               axis=1)
        nwA = jnp.maximum(af.sh.cts[dnode], runsN[dnode, iota])
        nrA = jnp.maximum(nwA + 1, mrts)
        onehot_d = (jnp.arange(NR, dtype=i32)[:, None] == drep[None, :]) \
            & dr[None, :]
        runsA = jax.lax.cummax(jnp.where(onehot_d, nwA[None, :], NEG),
                               axis=1)
        nwB = jnp.maximum(af.rp.cts[drep], runsA[drep, iota])
        nrB = jnp.maximum(nwB + 1, nrA)

        # ---- installs: shared tier at the drained node, then the
        # drained replica's tier (no pending lines — fences only drain)
        thA, wayA = S.probe(af.sh.tag, dnode, ds2, dkey)
        sh2, shg2, ev1 = _tier_install(
            af.sh, af.sh_gseq, dnode, ds2, dkey, nwA, nrA, dver, gs,
            lru_sh, thA & dr, wayA, dr, W2)
        thB, wayB = S.probe(af.rp.tag, drep, ds1, dkey)
        rp2, rpg2, ev2 = _tier_install(
            af.rp, af.rp_gseq, drep, ds1, dkey, nwB, nrB, dver, gs,
            lru_rp, thB & dr, wayB, dr, W1)

        # ---- counters: the op-scan's per-drain calls, summed
        n = lambda b: jnp.sum(b2i(b))
        Dn = n(dr)
        cross = dr & (dshard != dnode % KS)
        _, b2m, big = S.link_bytes(jnp.int32(0), Dn, n(cross))
        g2 = af.g + _gsum(
            l2_to_mm=Dn, write_throughs=Dn, pcie_blocks=n(cross),
            tsu_evictions=n(evict), overflow_reinits=n(ovf),
            capacity_evictions=n(ev1) + n(ev2),
            bytes_l2_mm=b2m, bytes_inter_gpu=big)
        r2 = af.r.at[drep, RI["write_throughs"]].add(b2i(dr))
        r2 = r2.at[drep, RI["capacity_evictions"]].add(b2i(ev2))

        af = af._replace(
            rp=rp2._replace(cts=jnp.maximum(af.rp.cts, runsA[:, -1])),
            rp_gseq=rpg2,
            sh=sh2._replace(cts=jnp.maximum(af.sh.cts, runsN[:, -1])),
            sh_gseq=shg2,
            tsu=tsu2, tsu_ver=ver2, tsu_gseq=gseq2, tsu_seq=seq2,
            tsu_nseq=nseq2, gseq_next=gnext2, g=g2, r=r2)

        vals = jnp.stack([
            b2i(dr), jnp.where(dr, dkey, -1),
            jnp.where(dr, dver, -1), jnp.where(dr, mwts, -1),
            jnp.where(dr, mrts, -1), jnp.where(dr, gs, -1),
        ])                                       # WRITE_RES_FIELDS order
        return af, jnp.where(act[None, :], vals, out)

    def pass_(af, sched, masks, rd, wr):
        ent_l = sched[0].astype(bool)
        dkey = sched[1]
        drep = jnp.clip(sched[2], 0, NR - 1)
        dwl = sched[3]
        dshard = sched[4]
        ds1 = sched[5]
        ds2 = sched[6]
        dnode = jnp.clip(sched[7], 0, NN - 1)
        D = dkey.shape[0]
        iota = jnp.arange(D, dtype=i32)

        # ---- lane-static bookkeeping: LRU ranks from the batch-start
        # ticks, tick/ring advances applied once (nothing in-scan reads
        # them — the schedule block carries every drained entry)
        onehot_d = (jnp.arange(NR, dtype=i32)[:, None] == drep[None, :]) \
            & ent_l[None, :]
        onehot_n = (jnp.arange(NN, dtype=i32)[:, None] == dnode[None, :]) \
            & ent_l[None, :]
        cr = jnp.cumsum(b2i(onehot_d), axis=1)
        cn = jnp.cumsum(b2i(onehot_n), axis=1)
        lru_rp = af.rp_tick[drep] + cr[drep, iota]
        lru_sh = af.sh_tick[dnode] + cn[dnode, iota]
        cnt_n = cn[:, -1]
        af = af._replace(
            rp_tick=af.rp_tick + cr[:, -1],
            sh_tick=af.sh_tick + cnt_n,
            wq_head=(af.wq_head + cnt_n) % Q,
            wq_len=af.wq_len - cnt_n,
            g=af.g + _gsum(fences=jnp.int32(1)))

        out0 = jnp.zeros((len(WRITE_RES_FIELDS), D), i32)

        def step(carry, act):
            af, out = carry
            return round_body(af, out, act, ent_l, dkey, drep, dwl,
                              dshard, ds1, ds2, dnode, lru_rp, lru_sh,
                              rd, wr), None

        (af, out), _ = jax.lax.scan(step, (af, out0), masks)

        # ---- §11b barrier: every client clock jumps to the global max
        gmax = jnp.maximum(jnp.max(af.rp.cts), jnp.max(af.sh.cts))
        af = af._replace(
            rp=af.rp._replace(cts=jnp.full_like(af.rp.cts, gmax)),
            sh=af.sh._replace(cts=jnp.full_like(af.sh.cts, gmax)))
        return af, out, gmax

    return pass_


# -------------------------------------------------- collective accounting
def collective_counts(jaxpr) -> dict:
    """Walk a (closed) jaxpr and count collective primitives: ``total``
    occurrences and how many sit inside a scan/while body (``in_loop``).
    A collective inside a loop executes once PER ITERATION — the exact
    O(ops)-collectives failure mode the batched pipeline removes — so the
    parity suite pins ``in_loop == 0`` and ``total`` == the per-batch
    collective budget for ``pipeline="batched"``.  (The miss pass's round
    scan is collective-free: its one gather sits OUTSIDE the scan.)

    The walker itself now lives in ``repro.obs.xprof`` (the observability
    layer's static cost probe, which also reports per-primitive counts
    and compiled FLOPs/bytes); this wrapper keeps the parity suite's
    two-field view."""
    from repro.obs.xprof import jaxpr_collectives

    c = jaxpr_collectives(jaxpr)
    return {"total": c["total"], "in_loop": c["in_loop"]}
