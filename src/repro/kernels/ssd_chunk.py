"""Mamba2 SSD intra-chunk kernel (Pallas).

Grid (B, nc, H): each step computes one chunk's quadratic intra-chunk output
and its state summary with everything ([Q,Q] decay/score tiles) resident in
VMEM.  The cheap sequential inter-chunk pass stays in jnp (repro.models.ssm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import interpret_mode


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, cum_ref):
    x = x_ref[0, 0, :, 0].astype(jnp.float32)          # [Q, P]
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)        # [Q]
    A = a_ref[0].astype(jnp.float32)                   # scalar
    B = b_ref[0, 0, :, 0].astype(jnp.float32)          # [Q, N]
    C = c_ref[0, 0, :, 0].astype(jnp.float32)          # [Q, N]
    Q = x.shape[0]

    cum = jnp.cumsum(dt * A)                           # [Q]
    li = cum[:, None] - cum[None, :]
    tril = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    L = jnp.exp(jnp.where(tril, li, -jnp.inf))
    scores = (C @ B.T) * L * dt[None, :]
    y_ref[0, 0, :, 0] = (scores @ x).astype(y_ref.dtype)
    decay_out = jnp.exp(cum[-1] - cum)
    st_ref[0, 0, 0] = ((B * (dt * decay_out)[:, None]).T @ x).astype(st_ref.dtype)
    cum_ref[0, 0, :, 0] = cum.astype(cum_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(x, dt, A, Bc, Cc, *, interpret=None):
    """x: [B,nc,Q,H,P]; dt: [B,nc,Q,H]; A: [H]; Bc/Cc: [B,nc,Q,H,N].

    Returns (y_intra [B,nc,Q,H,P], chunk_state [B,nc,H,N,P], cum [B,nc,Q,H]).
    (B/C already broadcast from groups to heads.)"""
    Bs, nc, Q, H, P = x.shape
    N = Bc.shape[-1]
    grid = (Bs, nc, H)
    y, st, cum = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, c, h: (b, c, 0, h)),
            pl.BlockSpec((1,), lambda b, c, h: (h,)),
            pl.BlockSpec((1, 1, Q, 1, N), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, 1, N), lambda b, c, h: (b, c, 0, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, c, h: (b, c, 0, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bs, nc, Q, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bs, nc, H, N, P), jnp.float32),
            jax.ShapeDtypeStruct((Bs, nc, Q, H), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(x, dt, A, Bc, Cc)
    return y, st, cum
