"""HALCONE fused miss/write-pass round kernels.

The batched grant pipeline's round bodies (``coherence.fabric.pipeline``)
are built from per-lane decision math that would otherwise run as two
separate ``lease_probe`` launches plus a dozen gather/select XLA ops per
round.  The ``[R, M]`` round masks and the prefix-sum LRU/drain schedules
are all static-shaped, so the whole per-lane decision surface fuses into
ONE Pallas grid pass over the request lanes, the way ``kernels.lease_probe``
fused probe+install for the op-scan:

  * ``miss_round`` — the read-side round math: replica probe, shared
    probe, TSU read grant (Algorithm 3 + the 16-bit overflow reinit) and
    BOTH install levels (Algorithms 1/2) in one kernel.  Serves
    ``pipeline.make_miss_pass``; the state scatters (self-invalidation,
    LRU touch/fill, TSU commit) stay outside — they are cross-lane.
  * ``write_grant`` — the write-side TSU math: probe, lexicographic
    victim (min-``(memts, alloc_seq)``, the host ``TSUShard`` dict-order
    rule), ``mm_write`` grant + overflow reinit.  Serves
    ``core.state.tsu_commit_write_batch`` (the write AND fence passes).

Everything is int32 lattice math — no floats — so fusion is bit-exact by
construction; the parity suites pin it to ``HostFabric`` end to end.

Layout and backend follow ``kernels.lanes`` (DESIGN.md §12c): requests on
the lane axis, the ways of each gathered set row — up to a whole TSU
shard — down the sublanes of one block, so way reductions (first match,
victim) never cross block boundaries; compiled with Mosaic on the TPU,
interpret mode on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.protocol import TS_MAX
from repro.kernels.lanes import at_first, first_index, lane_call

_INVALID = -1          # core.state.INVALID (empty way); pinned by tests
_NEG = -2 ** 30
_MISS_OUTS = 16
_WRITE_OUTS = 7


def _probe(tags, addr):
    """(tag hit, first matching way or 0, match mask) of one set row."""
    eq = tags == addr
    idx, _ = first_index(eq)
    hit = idx < tags.shape[0]
    return hit, jnp.where(hit, idx, 0), eq


def _miss_round_kernel(rp_tag_ref, rp_rts_ref, sh_tag_ref, sh_rts_ref,
                       sh_wts_ref, ts_tag_ref, ts_mem_ref, vec_ref, out_ref):
    cts1, cts2, addr, act, rd = (vec_ref[j:j + 1, :] for j in range(5))
    act = act != 0

    # ---- replica probe (first-match way + protocol.valid)
    th1, way1, eq1 = _probe(rp_tag_ref[...], addr)
    h1 = th1 & (cts1 <= at_first(eq1, rp_rts_ref[...]))
    th1, h1 = th1 & act, h1 & act
    miss = act & ~h1

    # ---- shared probe (only meaningful on a replica miss)
    th2, way2, eq2 = _probe(sh_tag_ref[...], addr)
    rts2 = at_first(eq2, sh_rts_ref[...])
    wts2 = at_first(eq2, sh_wts_ref[...])
    h2 = th2 & (cts2 <= rts2)
    th2, h2 = th2 & miss, h2 & miss
    need = miss & ~h2

    # ---- TSU read grant (Algorithm 3 + 16-bit overflow reinit)
    tht, tway, eqt = _probe(ts_tag_ref[...], addr)
    memts = at_first(eqt, ts_mem_ref[...])        # 0 when absent
    mwts = memts                                  # protocol.mm_read
    mrts = memts + rd
    nmem = mrts
    ovf = nmem > TS_MAX
    mwts = jnp.where(ovf, 0, mwts)
    mrts = jnp.where(ovf, rd, mrts)
    nmem = jnp.where(ovf, mrts, nmem)
    fnd = need & tht

    # ---- response chain: install at shared, then at the replica
    nwa = jnp.maximum(cts2, mwts)                 # protocol.install
    nra = jnp.maximum(nwa + 1, mrts)
    rwts = jnp.where(h2, wts2, nwa)
    rrts = jnp.where(h2, rts2, nra)
    nw1 = jnp.maximum(cts1, rwts)
    nr1 = jnp.maximum(nw1 + 1, rrts)

    i32 = jnp.int32
    for j, v in enumerate((th1.astype(i32), h1.astype(i32), way1,
                           th2.astype(i32), h2.astype(i32), way2,
                           fnd.astype(i32), tway, mwts, mrts, nmem,
                           (fnd & ovf).astype(i32), nwa, nra, nw1, nr1)):
        out_ref[j:j + 1, :] = v


def _write_grant_kernel(ts_tag_ref, ts_mem_ref, ts_seq_ref, vec_ref,
                        out_ref):
    i32 = jnp.int32
    addr, wl = vec_ref[0:1, :], vec_ref[1:2, :]
    tags = ts_tag_ref[...]
    mem = ts_mem_ref[...]

    th, way, eq = _probe(tags, addr)
    # lexicographic victim: invalid first, else min memts, ties broken by
    # min alloc seq (state.victim_lex — the host dict-order rule); the
    # first such way, as argmin picks it
    invalid = tags == _INVALID
    p = jnp.where(invalid, i32(_NEG), mem)
    pmin = jnp.min(p, axis=0, keepdims=True)
    s = jnp.where(p == pmin, ts_seq_ref[...], i32(2 ** 30))
    smin = jnp.min(s, axis=0, keepdims=True)
    vic, _ = first_index(s == smin)
    w0 = jnp.where(th, way, vic)
    n_valid = jnp.sum((~invalid).astype(i32), axis=0, keepdims=True)
    full = n_valid == tags.shape[0]

    memts = at_first(eq, mem)                     # 0 when absent
    wts = memts + 1                               # protocol.mm_write
    rts = memts + wl
    nmem = rts
    ovf = nmem > TS_MAX
    wts = jnp.where(ovf, 0, wts)
    rts = jnp.where(ovf, wl, rts)
    nmem = jnp.where(ovf, rts, nmem)

    for j, v in enumerate((th.astype(i32), w0, full.astype(i32), wts, rts,
                           nmem, ovf.astype(i32))):
        out_ref[j:j + 1, :] = v


@functools.partial(jax.jit, static_argnames=("interpret",))
def miss_round(rp_tag, rp_rts, sh_tag, sh_rts, sh_wts, ts_tag, ts_mem,
               cts1, cts2, addr, act, rd, *, interpret=None):
    """Fused read-side round math over gathered set rows.

    rp_tag/rp_rts: [N, W1] live replica-set ways; sh_tag/sh_rts/sh_wts:
    [N, W2] live shared-set ways; ts_tag/ts_mem: [N, C] the TSU shard's
    fully-associative set; cts1/cts2/addr/act/rd: [N] int32 (act is the
    round mask as 0/1; rd the read lease, broadcast).

    Returns 16 int32 [N] vectors — exactly the intermediates of
    ``make_miss_pass``'s round body:
      th1/h1/way1     — replica tag hit (act-masked), valid hit, way
      th2/h2/way2     — shared tag/valid hit (replica-miss-masked), way
      fnd/tway        — TSU entry found (= miss & ~h2 & tag hit), way
      mwts/mrts/nmem  — TSU read grant + new entry clock (raw, unmasked)
      ovf             — grant re-initialized the entry (fnd-masked)
      nwa/nra         — install at the shared tier (protocol.install)
      nw1/nr1         — install at the replica of the response lease
                        (shared hit's lease when h2, else nwa/nra)
    """
    out = lane_call(_miss_round_kernel,
                    [rp_tag, rp_rts, sh_tag, sh_rts, sh_wts, ts_tag, ts_mem],
                    [cts1, cts2, addr, act, rd], _MISS_OUTS, interpret)
    b = lambda x: x.astype(bool)
    (th1, h1, way1, th2, h2, way2, fnd, tway, mwts, mrts, nmem, ovf, nwa,
     nra, nw1, nr1) = out
    return (b(th1), b(h1), way1, b(th2), b(h2), way2, b(fnd), tway, mwts,
            mrts, nmem, b(ovf), nwa, nra, nw1, nr1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_grant(ts_tag, ts_mem, ts_seq, addr, wl, *, interpret=None):
    """Fused write-side TSU math over gathered shard rows.

    ts_tag/ts_mem/ts_seq: [N, C] the TSU shard's live ways (tag, entry
    clock, allocation sequence); addr/wl: [N] int32 (wl = the effective
    write lease per lane).

    Returns (th, way, full, wts, rts, nmem, ovf), int32/bool [N]:
      th   — tag hit;  way — the hit way, else the lexicographic victim
      full — every live way is allocated (eviction iff ~th & full)
      wts/rts/nmem/ovf — ``mm_write`` grant + overflow reinit (raw;
      inactive-lane masking is the caller's).
    """
    out = lane_call(_write_grant_kernel, [ts_tag, ts_mem, ts_seq],
                    [addr, wl], _WRITE_OUTS, interpret)
    th, way, full, wts, rts, nmem, ovf = out
    return (th.astype(bool), way, full.astype(bool), wts, rts, nmem,
            ovf.astype(bool))
