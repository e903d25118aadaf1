"""Fused RMSNorm Pallas kernel: one HBM read + one write per row block
(XLA's unfused chain reads x three times)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import interpret_mode


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * (1.0 + w_ref[...].astype(jnp.float32))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "br", "interpret"))
def rmsnorm(x, w, *, eps=1e-6, br=256, interpret=None):
    """x: [..., D]; w: [D]."""
    orig = x.shape
    D = orig[-1]
    R = 1
    for d in orig[:-1]:
        R *= d
    x2 = x.reshape(R, D)
    br = min(br, R)
    while R % br:
        br -= 1
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret_mode(interpret),
    )(x2, w)
    return out.reshape(orig)
