"""Array-native coherence state layer: the ONE implementation of the
hierarchy transition rules.

HALCONE's pitch is that every coherence decision is local arithmetic over
``[wts, rts]`` leases — so the whole hierarchy (L1/replica tier, L2/shared
tier, TSU) is representable as a handful of int32 arrays plus pure, batched
transition functions.  This module holds exactly that:

  * ``TierState``  — one set-associative lease tier ([N, S, W+1] arrays with
    a trailing trash way for masked scatters) — the simulator's L1 and L2
    AND the fabric's replica/shared client tiers.
  * ``TSUState``   — the timestamp-storage-unit rows (tag + 16-bit memts) —
    the simulator's per-HBM-stack TSU AND the fabric's per-shard MM+TSU
    table (shaped ``[n_shards, 1, capacity+1]``, i.e. one fully-associative
    set per shard).
  * transition functions — probe / victim selection / the TSU grant
    (Algorithm 3 + 16-bit overflow reinit) / the fused tier probe+install
    (Algorithms 1, 2, 4, 5 via ``kernels.lease_probe``) / the TSU commit.
  * packed buffers + batched rules — each tier's arrays as ONE contiguous
    buffer (``pack_tier``/``pack_tsu``), the grouped-by-owner shard
    exchange (``owner_gather``/``owner_take``), and the whole-batch TSU
    transition (``tsu_lease_batch``/``tsu_commit_batch``) that the
    batched grant pipeline (DESIGN.md §9) is built from.  The per-op
    rules above remain the oracle these must match bit-for-bit.

Both consumers import from here and re-derive NOTHING:

  * ``core/engine.py`` — the timing simulator: one ``round_step`` scan,
    requests batched over all CUs.
  * ``coherence/fabric/arrays.py`` — the production fabric: one op-scan,
    requests batched per serving/training batch.

All timestamp arithmetic is ``repro.core.protocol``; all fused probe+install
math is ``kernels.lease_probe`` (compiled Pallas on TPU/GPU, interpret
fallback on CPU — bit-identical, see DESIGN.md §5).  No other module may
implement these rules (DESIGN.md §7 backend-parity contract).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import protocol
# module imports (not names): kernels.tier_pass imports core.protocol, so
# importing it first must not need this module fully initialized
from repro.kernels import lease_probe as probe_kernel
from repro.kernels import tier_pass

INVALID = jnp.int32(-1)

# ------------------------------------------------------- link traffic (Fig 10)
# Every hierarchy hop moves one data block; directory invalidations (HMG)
# are control-sized messages.  These two constants + ``link_bytes`` are the
# ONE definition of the paper's Fig-10 per-link traffic accounting: the
# timing simulator (engine.COUNTERS) and the production fabric
# (FabricStats) both report bytes through this helper, so a simulated
# trace and a served trace decompose identically.
BLOCK_BYTES = 64        # one cache block / KV line on any data link
CTRL_BYTES = 8          # one invalidation / control message (HMG only)


def link_bytes(l1_l2_msgs, l2_mm_msgs, inter_gpu_blocks, inval_msgs=0):
    """Per-link byte counters (L1<->L2, L2<->MM, inter-GPU).

    Works on python ints and on traced arrays alike.  HALCONE's headline
    (Fig. 10): ``inval_msgs`` is 0 by construction, so its inter-GPU bytes
    are pure data; HMG pays ``CTRL_BYTES`` per invalidation on the same
    low-bandwidth links.
    """
    return (l1_l2_msgs * BLOCK_BYTES,
            l2_mm_msgs * BLOCK_BYTES,
            inter_gpu_blocks * BLOCK_BYTES + inval_msgs * CTRL_BYTES)


# ------------------------------------------------------ per-op result block
# The packed per-op result record shared by the fabric's batched miss pass
# (coherence/fabric/pipeline.py, [7, M]) and the simulator's round step
# (core/engine.py, [7, NC] per round): field order is the layout contract
# for the stacked int32 buffer both emit, so serving traces and figure
# sweeps decode per-op results identically (ROADMAP miss-pass telemetry).
#   found    1 iff the op produced/committed a value
#   version  data version returned (reads) or committed (writes); -1 none
#   gseq     payload write-sequence handle (fabric only; simulator: -1)
#   level    read service level 0=L1 1=L2 2=peer/home 3=MM; -1 non-read
#   wts/rts  the lease installed at the top tier (0 when none)
#   mm_used  1 iff the op reached the MM/TSU authority
RES_FIELDS = ("found", "version", "gseq", "level", "wts", "rts", "mm_used")


# ----------------------------------------------------------------- states
class TierState(NamedTuple):
    """One set-associative lease tier.

    Arrays are ``[N, S, W+1]`` (N caches x S sets x W ways + 1 trash way
    used as the target of masked scatters; a real tag never lands there).
    ``cts`` is the per-cache logical clock ``[N]``.
    """

    tag: jnp.ndarray     # int32, INVALID = empty
    wts: jnp.ndarray
    rts: jnp.ndarray
    ver: jnp.ndarray     # data version carried by the line
    lru: jnp.ndarray     # victim score (higher = more recently used)
    cts: jnp.ndarray     # [N] logical clocks

    @property
    def n_ways(self) -> int:
        return self.tag.shape[-1] - 1


class TSUState(NamedTuple):
    """Timestamp-storage-unit rows: ``[H, S, W+1]`` tag + memts."""

    tag: jnp.ndarray
    memts: jnp.ndarray

    @property
    def n_ways(self) -> int:
        return self.tag.shape[-1] - 1


def init_tier(n: int, sets: int, ways: int) -> TierState:
    shp = (n, sets, ways + 1)
    z = lambda: jnp.zeros(shp, jnp.int32)
    return TierState(tag=jnp.full(shp, INVALID), wts=z(), rts=z(), ver=z(),
                     lru=z(), cts=jnp.zeros((n,), jnp.int32))


def init_tsu(h: int, sets: int, ways: int) -> TSUState:
    shp = (h, sets, ways + 1)
    return TSUState(tag=jnp.full(shp, INVALID),
                    memts=jnp.zeros(shp, jnp.int32))


# ----------------------------------------------------------------- probes
def probe(tag_arr, idx, set_idx, addr):
    """Tag-only probe over the live ways of each request's set.

    tag_arr: [N, S, W+1]; idx/set_idx/addr: [n].  Returns (tag_hit, way) —
    ``way`` is the FIRST matching way (argmax over the match mask), the
    convention every consumer and the Pallas kernel share.
    """
    rows = tag_arr[idx, set_idx][..., :-1]          # [n, W]
    eq = rows == addr[..., None]
    return eq.any(-1), jnp.argmax(eq, -1)


def victim(tag_arr, score_arr, idx, set_idx):
    """Victim way: invalid ways first, else the minimum score; ties break to
    the FIRST such way (argmin), matching the host stores' strict-< scan."""
    rows_t = tag_arr[idx, set_idx][..., :-1]
    rows_s = score_arr[idx, set_idx][..., :-1]
    score = jnp.where(rows_t == INVALID, jnp.int32(-2 ** 30), rows_s)
    return jnp.argmin(score, -1)


def victim_lex(tag_arr, primary, secondary, idx, set_idx):
    """Lexicographic victim: invalid first, else min primary, ties broken by
    min secondary (the fabric TSU's dict-order rule: among equal-``memts``
    entries the earliest-allocated is evicted)."""
    rows_t = tag_arr[idx, set_idx][..., :-1]
    rows_p = primary[idx, set_idx][..., :-1]
    rows_s = secondary[idx, set_idx][..., :-1]
    invalid = rows_t == INVALID
    p = jnp.where(invalid, jnp.int32(-2 ** 30), rows_p)
    pmin = jnp.min(p, -1, keepdims=True)
    s = jnp.where(p == pmin, rows_s, jnp.int32(2 ** 30))
    return jnp.argmin(s, -1)


# ------------------------------------------------------------- TSU grant
class TSUGrant(NamedTuple):
    wts: jnp.ndarray        # the [wts, rts] lease the TSU grants
    rts: jnp.ndarray
    new_memts: jnp.ndarray  # the clock the entry holds afterwards
    overflow: jnp.ndarray   # bool: the 16-bit reinit fired


def tsu_lease(memts, is_write, rd_lease, wr_lease) -> TSUGrant:
    """The TSU decision (Algorithm 3, Fig. 5 conventions) for a batch of
    requests against their entries' current clocks, including the 16-bit
    overflow reinit (DESIGN.md §3a): a grant that would push ``memts`` past
    ``protocol.TS_MAX`` restarts the entry at 0 and is re-served as a first
    read — wts=0, rts=lease, memts'=rts (write-through keeps MM correct).

    memts: [n] current entry clocks (0 for fresh/missing entries);
    is_write: [n] bool; rd_lease/wr_lease: scalars or [n].
    """
    r_lease, r_memts = protocol.mm_read(memts, rd_lease)
    w_lease, w_memts = protocol.mm_write(memts, wr_lease)
    wts = jnp.where(is_write, w_lease.wts, r_lease.wts)
    rts = jnp.where(is_write, w_lease.rts, r_lease.rts)
    new_memts = jnp.where(is_write, w_memts, r_memts)
    ovf = new_memts > protocol.TS_MAX
    wts = jnp.where(ovf, 0, wts)
    rts = jnp.where(ovf, jnp.where(is_write, wr_lease, rd_lease), rts)
    new_memts = jnp.where(ovf, rts, new_memts)
    return TSUGrant(wts, rts, new_memts, ovf)


def tsu_commit_scatter(tsu: TSUState, idx, set_idx, way, addr, new_memts,
                       active, tag_hit) -> TSUState:
    """The simulator's TSU state update: same-round requests to one slot are
    resolved by scatter-max (same-tick semantics, paper §3.2 — the largest
    extension wins; on an eviction-install the largest tag keeps the slot).
    Inactive requests are routed to the trash way.
    """
    tw = jnp.where(active, way, tsu.n_ways)
    tag = tsu.tag.at[idx, set_idx, tw].max(
        jnp.where(active, addr, INVALID))
    cleared = jnp.where(active & ~tag_hit, 0, tsu.memts[idx, set_idx, tw])
    memts = tsu.memts.at[idx, set_idx, tw].set(
        jnp.where(active, jnp.maximum(cleared, 0), cleared))
    memts = memts.at[idx, set_idx, tw].max(jnp.where(active, new_memts, 0))
    return TSUState(tag=tag, memts=memts)


def tsu_commit_exact(tsu: TSUState, idx, set_idx, way, addr, new_memts,
                     active) -> TSUState:
    """The fabric's TSU state update: one op at a time, so the slot is
    written exactly (the host dict's replace semantics — no scatter-max
    races to resolve).  Inactive ops are routed to the trash way."""
    tw = jnp.where(active, way, tsu.n_ways)
    return TSUState(
        tag=tsu.tag.at[idx, set_idx, tw].set(
            jnp.where(active, addr, tsu.tag[idx, set_idx, tw])),
        memts=tsu.memts.at[idx, set_idx, tw].set(
            jnp.where(active, new_memts, tsu.memts[idx, set_idx, tw])))


# -------------------------------------------------- tier probe + install
def install_lease(cts, wts_resp, rts_resp):
    """Install math alone (Algorithms 1/2 + writer clock), for fills whose
    way is already known: returns (new_wts, new_rts, new_cts).  The same
    arithmetic ``tier_probe`` fuses with the probe via the Pallas kernel."""
    lease = protocol.install(cts, wts_resp, rts_resp)
    return lease.wts, lease.rts, protocol.cts_after_write(cts, lease.wts)


def tier_probe(tier: TierState, idx, set_idx, addr, mwts, mrts):
    """Fused probe + install math for one tier — the per-request coherence
    action, served by the Pallas lease-probe kernel.

    Gathers each request's set row from ``tier`` and runs the kernel:
    tag compare (first-match way), lease validity (``protocol.valid``),
    Algorithm 1/2 install (``protocol.install``) of the response lease
    ``(mwts, mrts)`` arriving from the level below, and the writer clock
    advance (``protocol.cts_after_write``).

    Returns (tag_hit, hit, way, row_rts, new_wts, new_rts, new_cts); see
    ``kernels.lease_probe`` for the exact contract.  Callers that only need
    the probe half may pass zeros for (mwts, mrts) and ignore the install
    outputs; callers that only need the install half ignore the hit outputs.
    """
    return probe_kernel.lease_probe(tier.tag[idx, set_idx][..., :-1],
                                    tier.rts[idx, set_idx][..., :-1],
                                    tier.cts[idx], addr, mwts, mrts)


# ------------------------------------------------- packed contiguous buffers
# The batched grant pipeline (coherence/fabric, DESIGN.md §9) moves tier /
# TSU state as ONE contiguous buffer per tier: packing turns the per-batch
# cross-shard exchange into a single collective and the per-request row
# access into a single gather.  Field order is part of the layout contract.
TIER_FIELDS = ("tag", "wts", "rts", "ver", "lru")
TSU_FIELDS = ("tag", "memts", "ver", "gseq", "seq", "nseq")


def pack_tier(tier: TierState) -> jnp.ndarray:
    """Per-tier arrays as ONE contiguous ``[5, N, S, W+1]`` buffer
    (``TIER_FIELDS`` order; ``cts`` stays separate — it is per-cache, not
    per-line)."""
    return jnp.stack([tier.tag, tier.wts, tier.rts, tier.ver, tier.lru])


def unpack_tier(buf: jnp.ndarray, cts: jnp.ndarray) -> TierState:
    return TierState(tag=buf[0], wts=buf[1], rts=buf[2], ver=buf[3],
                     lru=buf[4], cts=cts)


def pack_tsu(tsu: TSUState, ver, gseq, seq, nseq) -> jnp.ndarray:
    """The TSU tier plus its per-shard sequencers as ONE contiguous
    ``[6, H, S, W+1]`` buffer (``TSU_FIELDS`` order) — the payload of the
    batched pipeline's one-collective-per-batch shard exchange.  ``nseq``
    is ``[H]``; it rides in field 5 at ``[:, 0, 0]`` (the rest of that
    plane is padding, never read back)."""
    f5 = jnp.zeros_like(tsu.tag).at[:, 0, 0].set(nseq)
    return jnp.stack([tsu.tag, tsu.memts, ver, gseq, seq, f5])


def unpack_tsu(buf: jnp.ndarray) -> Tuple:
    """Inverse of ``pack_tsu``: (TSUState, ver, gseq, seq, nseq)."""
    return (TSUState(tag=buf[0], memts=buf[1]), buf[2], buf[3], buf[4],
            buf[5][:, 0, 0])


def owner_gather(packed: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Grouped-by-owner gather: assemble the full shard-major buffer from
    every device's contiguous owned rows — ONE ``all_gather`` over the
    mesh axis, the batched pipeline's single per-batch collective.

    packed: ``[F, H_local, ...]`` (this device's rows).  Returns
    ``[F, H_local * D, ...]`` with device ``d``'s rows at
    ``[d*H_local, (d+1)*H_local)`` — the same shard-major placement
    ``NamedSharding`` lays out."""
    full = jax.lax.all_gather(packed, axis_name)        # [D, F, Hl, ...]
    full = jnp.moveaxis(full, 0, 1)                     # [F, D, Hl, ...]
    return full.reshape((full.shape[0],
                         full.shape[1] * full.shape[2]) + full.shape[3:])


def owner_take(packed_full: jnp.ndarray, me, rows: int) -> jnp.ndarray:
    """Grouped-by-owner scatter (the no-communication half): slice this
    device's contiguous ``rows`` shard rows back out of the full buffer."""
    return jax.lax.dynamic_slice_in_dim(packed_full, me * rows, rows, axis=1)


def tsu_commit_batch(tsu: TSUState, idx, set_idx, way, addr, new_memts,
                     active) -> TSUState:
    """Batched exact TSU commit: one scatter for a whole batch of grants.

    Same slot semantics as ``tsu_commit_exact`` (the host dict's replace),
    vectorized — the caller must guarantee that no two ACTIVE requests in
    the batch target the same ``(idx, set_idx, way)`` slot (one request
    per key per call; distinct keys always occupy distinct slots).
    Inactive requests are routed to the trash way and write back the
    slot's original values."""
    return tsu_commit_exact(tsu, idx, set_idx, way, addr, new_memts, active)


def tsu_commit_write_batch(tsu: TSUState, ver_arr, gseq_arr, seq_arr, nseq,
                           gseq0, shard, key, wr_eff, rd_lease, active):
    """The batched write-side TSU transition: ONE probe + allocation +
    grant + commit for a whole batch of write-throughs (the ``mm_write``
    half of the batched write pass, DESIGN.md §11 — mirrors
    ``tsu_lease_batch`` the way writes mirror reads).

    Per request: probe the shard's fully-associative set; on a miss,
    allocate — evicting the min-``(memts, alloc_seq)`` entry when the
    shard is full (``victim_lex``, the host ``TSUShard`` dict-order
    rule); grant via Algorithm 3 as a write (+ the 16-bit overflow
    reinit) against the entry's current clock; bump the version
    (``ver+1`` in place, 1 on a fresh allocation) and stamp the grant
    with a globally unique write-sequence id ``gseq0 + rank`` — all
    vectorized, one scatter per side array.

    Requires DISTINCT active keys AND at most one active write per
    shard per call: a second allocation in one shard is sequentially
    coupled to the first through the victim choice and the per-shard
    allocation sequencer, so the write pass's conflict rounds
    (``pipeline.write_schedule``) never co-schedule two TSU writes to
    one shard.

    shard/key/wr_eff: [n] (``wr_eff`` is the already-resolved write
    lease — the op's override or the config default); active: [n] bool.
    Returns ``(wts, rts, ver, gs, evict, overflow, new_tsu, new_ver,
    new_gseq, new_seq, new_nseq, new_gseq_next)``: wts/rts/ver/gs are
    the grant fields (gs = -1 on inactive lanes), ``evict`` flags
    full-set victim evictions, ``overflow`` flags grants that
    re-initialized the entry."""
    i32 = jnp.int32
    b2i = lambda b: b.astype(i32)
    zset = jnp.zeros_like(shard)
    cap = tsu.n_ways
    # fused probe + lex victim + mm_write grant (ONE Pallas grid pass —
    # kernels.tier_pass.write_grant, the write-side twin of the miss
    # round's fused kernel; same victim_lex/tsu_lease math, bit-exact)
    th, w0, full, g_wts, g_rts, g_memts, g_ovf = tier_pass.write_grant(
        tsu.tag[shard, zset][..., :-1], tsu.memts[shard, zset][..., :-1],
        seq_arr[shard, zset][..., :-1], key,
        jnp.broadcast_to(jnp.asarray(wr_eff, i32), key.shape))
    gr = TSUGrant(g_wts, g_rts, g_memts, g_ovf)
    evict = active & ~th & full
    ver = jnp.where(th, ver_arr[shard, zset, w0] + 1, 1)
    seqv = jnp.where(th, seq_arr[shard, zset, w0], nseq[shard])
    rank = jnp.cumsum(b2i(active)) - b2i(active)       # exclusive gseq rank
    gs = jnp.where(active, gseq0 + rank, -1)
    new_tsu = tsu_commit_batch(tsu, shard, zset, w0, key, gr.new_memts,
                               active)
    w = jnp.where(active, w0, cap)                     # trash-way routing

    def pt(a, v):
        return a.at[shard, zset, w].set(
            jnp.where(active, v, a[shard, zset, w]))

    new_nseq = nseq.at[jnp.where(active, shard, 0)].add(
        b2i(active & ~th))
    return (gr.wts, gr.rts, ver, gs, evict, active & gr.overflow, new_tsu,
            pt(ver_arr, ver), pt(gseq_arr, gs), pt(seq_arr, seqv),
            new_nseq, gseq0 + jnp.sum(b2i(active)))


def tsu_lease_batch(tsu: TSUState, ver_arr, gseq_arr, shard, key,
                    rd_lease, wr_lease, active):
    """The batched read-side TSU transition: ONE probe + grant + commit for
    a whole batch of requests (the ``mm_read`` half of the batched grant
    pipeline, DESIGN.md §9).

    Per request: probe the shard's fully-associative set, grant via
    Algorithm 3 (+ the 16-bit overflow reinit) against the entry's current
    clock, and commit the extended ``memts`` exactly — all vectorized.
    Requires DISTINCT active keys (one request per key per call; the
    pipeline's conflict-round grouping guarantees it), because the commit
    is a one-shot batched scatter.

    shard/key: [n]; active: [n] bool (inactive requests touch nothing).
    Returns (found, wts, rts, ver, gseq, overflow, new_tsu): ``found`` is
    active AND the entry exists; ver/gseq are -1 when not found;
    ``overflow`` flags found grants that re-initialized the entry."""
    zset = jnp.zeros_like(shard)
    th, way = probe(tsu.tag, shard, zset, key)
    found = active & th
    memts = jnp.where(th, tsu.memts[shard, zset, way], 0)
    gr = tsu_lease(memts, jnp.zeros(key.shape, bool), rd_lease, wr_lease)
    new = tsu_commit_batch(tsu, shard, zset, way, key, gr.new_memts, found)
    ver = jnp.where(found, ver_arr[shard, zset, way], -1)
    gs = jnp.where(found, gseq_arr[shard, zset, way], -1)
    return found, gr.wts, gr.rts, ver, gs, found & gr.overflow, new
