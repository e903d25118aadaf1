"""HALCONE lease-probe kernel: the protocol engine's hot inner loop
(tag compare + lease check + Algorithm 1/2 install math), batched over all
concurrent requests.  This is the paper's per-request coherence action as a
single fused VMEM pass — the Pallas face of repro.core.protocol, and since
the batched sweep engine (DESIGN.md §5) the op that serves every L1 and L2
probe+install inside ``core.engine``'s round step.

Layout and backend follow ``kernels.lanes``: requests on the lane axis,
ways down the sublanes; compiled with Mosaic on the TPU, interpret mode on
the CPU — bit-identical int32 math either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lanes import at_first, first_index, lane_call


def _probe_kernel(tag_ref, rts_ref, vec_ref, out_ref):
    tags = tag_ref[...]                                 # [W, bn]
    cts, addr, mwts, mrts = (vec_ref[j:j + 1, :] for j in range(4))
    eq = tags == addr
    idx, _ = first_index(eq)
    tag_hit = idx < tags.shape[0]
    # first-match way only: the engine can hold a stale duplicate of a tag
    # (coherence-miss installs go to a victim way while the expired copy
    # stays live), and the probe must read the same way argmax selects
    row_rts = at_first(eq, rts_ref[...])
    hit = tag_hit & (cts <= row_rts)                    # protocol.valid
    # protocol.install: Bwts = max(cts, Mwts); Brts = max(Bwts+1, Mrts)
    bwts = jnp.maximum(cts, mwts)
    brts = jnp.maximum(bwts + 1, mrts)
    for j, v in enumerate((tag_hit.astype(jnp.int32), hit.astype(jnp.int32),
                           jnp.where(tag_hit, idx, 0), row_rts, bwts, brts,
                           jnp.maximum(cts, bwts))):    # cts_after_write
        out_ref[j:j + 1, :] = v


@functools.partial(jax.jit, static_argnames=("interpret",))
def lease_probe(tag_rows, rts_rows, cts, addr, mwts, mrts, *,
                interpret=None):
    """Fused probe + install over gathered set rows.

    tag_rows/rts_rows: [N, W] live ways of each request's set; cts/addr/
    mwts/mrts: [N] (int32).  (mwts, mrts) is the response lease arriving
    from the level below (TSU grant for an L2 probe, L2 response for an L1
    probe).

    Returns (tag_hit, hit, way, row_rts, new_wts, new_rts, new_cts):
      tag_hit  — tag match on a live way (coherency misses = tag_hit & ~hit)
      hit      — tag match AND lease valid (cts <= rts;  protocol.valid)
      way      — the first matching way (0 when no tag match)
      row_rts  — rts of the matching way (0 when no tag match)
      new_wts/new_rts — protocol.install(cts, mwts, mrts)
      new_cts  — protocol.cts_after_write(cts, new_wts)

    ``interpret=None`` applies the ``kernels.lanes`` backend rule."""
    out = lane_call(_probe_kernel, [tag_rows, rts_rows],
                    [cts, addr, mwts, mrts], 7, interpret)
    tag_hit, hit, way, row_rts, nwts, nrts, ncts = out
    return (tag_hit.astype(bool), hit.astype(bool), way, row_rts, nwts,
            nrts, ncts)
