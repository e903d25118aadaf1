"""The lane layout and backend rule shared by the lease kernels
(``lease_probe``, ``tier_pass``).

Each lease kernel serves one request per *lane*: it compares the request's
address against the ways of one gathered set row, reduces over the ways,
and applies the protocol's int32 lease math.  The TPU compiler (Mosaic)
tiles the last two dimensions of every block by (8, 128), so the request
axis goes on the 128-wide lane dimension:

  * each ``[N, W]`` row matrix a caller passes is handed to the kernel
    transposed, ``[W, N]`` — way reductions run down the sublanes, which
    Mosaic lowers for int32 (``min``/``sum``), unlike ``argmax``/``argmin``
    over ints and ``cumsum``, which it refuses;
  * the per-request vectors are packed into ONE ``[k, N]`` block and the
    kernel writes ONE ``[n_out, N]`` block, rows in the kernel's output
    order.

Lanes are blocked over a 1-D grid.  A whole lane axis that fits the block
budget is one block (any N, including the op-scan's N=1, is then legal:
a block equal to the array is always accepted); a longer one is padded to
a multiple of 128 and split into blocks whose width (a multiple of 128)
keeps the double-buffered blocks of the widest row — the TSU shard row —
inside ``_BLOCK_BYTES``.  ``_VMEM_LIMIT`` is set explicitly so the kernel's
reduction temporaries never lean on the compiler's default scoped limit.
Under ``jax.vmap`` (the figure engine) the batch axes become leading grid
axes and the blocks keep this (sublane, lane) shape.

The backend rule: a kernel compiles with Mosaic unless
``jax.default_backend()`` is the CPU, where Pallas has no lowering and the
kernel body runs in interpret mode — the identical int32 math as plain XLA
ops, so results are bit-identical across backends.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
_BLOCK_BYTES = 4 << 20        # one grid step's in + out blocks (x2 buffered)
_VMEM_LIMIT = 100 << 20       # of v5e's 128 MiB VMEM


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """The one rule for compiled versus interpreted kernels: an explicit
    ``interpret`` wins (tests), else interpret exactly on the CPU."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def lane_block(n: int, words: int):
    """Lanes per block and padded lane count for ``n`` lanes of ``words``
    int32 words each (all rows + packed vectors): ``(bn, n_pad)``."""
    cap = max(LANE, _BLOCK_BYTES // (2 * 4 * words) // LANE * LANE)
    if n <= cap:
        return n, n
    n_pad = -(-n // LANE) * LANE
    bn = cap
    while n_pad % bn:
        bn -= LANE
    return bn, n_pad


def first_index(eq):
    """Per lane, the index of the FIRST true way of ``eq`` ``[W, bn]``
    (``W`` when none) — argmax's first-match convention as a min over an
    iota.  Returns ``(idx [1, bn], iota [W, bn])``."""
    iota = jax.lax.broadcasted_iota(jnp.int32, eq.shape, 0)
    return jnp.min(jnp.where(eq, iota, eq.shape[0]), axis=0,
                   keepdims=True), iota


def at_first(eq, rows):
    """Value of ``rows`` at the FIRST true way of ``eq`` (0 when none)."""
    idx, iota = first_index(eq)
    return jnp.sum(jnp.where(iota == idx, rows, 0), axis=0, keepdims=True)


def lane_call(kernel: Callable, rows: Sequence, vecs: Sequence, n_out: int,
              interpret: Optional[bool] = None):
    """Run ``kernel(*row_refs, vec_ref, out_ref)`` over the request lanes.

    rows: ``[N, W_i]`` int32 row matrices (passed transposed, ``[W_i,
    N]``); vecs: ``[N]`` int32 vectors (packed ``[len(vecs), N]``, read
    in-kernel as ``vec_ref[j:j + 1, :]``).  Returns the ``[n_out, N]``
    int32 output block."""
    n = vecs[0].shape[0]
    widths = [r.shape[1] for r in rows]
    bn, n_pad = lane_block(n, sum(widths) + len(vecs) + n_out)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, n_pad - n))) if n_pad > n else a
    ins = [pad(r.T) for r in rows] + [pad(jnp.stack(vecs))]
    blk = lambda h: pl.BlockSpec((h, bn), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=[blk(w) for w in widths] + [blk(len(vecs))],
        out_specs=blk(n_out),
        out_shape=jax.ShapeDtypeStruct((n_out, n_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(interpret),
    )(*ins)
    return out[:, :n]
