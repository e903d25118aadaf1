"""Int8 gradient compression with error feedback.

Wire-format trick for the collective roofline term: gradients cross the ICI
as int8 (4x fewer bytes than f32, 2x fewer than bf16); the quantization error
is fed back into the next step's gradient so the optimizer sees an unbiased
long-run signal (standard EF-SGD result).

``compressed_psum``: shard_map ring — reduce-scatter in int8 chunks (local
dequant-accumulate in f32) then all-gather the int8 result.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize(x, axis=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def ef_compress(grad, error):
    """Error feedback: returns (decompressed_grad, new_error)."""
    g = grad.astype(jnp.float32) + error
    q, s = quantize(g)
    deq = dequantize(q, s)
    return deq.astype(grad.dtype), g - deq


def compressed_psum(x, axis_name: str, n: int):
    """Inside shard_map: int8-wire psum of a replicated-per-shard value.

    reduce-scatter(int8) -> local f32 accumulate -> all-gather(int8).
    Wire bytes: 2 * (n-1)/n * |x|/4 vs f32 all-reduce's 2 * (n-1)/n * |x|."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    q, s = quantize(chunks, axis=1)                  # per-chunk scales
    # exchange: every shard receives chunk i from all peers
    qx = jax.lax.all_to_all(q[None], axis_name, 0, 0, tiled=False)[:, 0]
    sx = jax.lax.all_to_all(s[None], axis_name, 0, 0, tiled=False)[:, 0]
    local_sum = jnp.sum(dequantize(qx, sx), axis=0)  # [chunk]
    q2, s2 = quantize(local_sum[None], axis=1)
    qg = jax.lax.all_gather(q2[0], axis_name)        # [n, chunk] int8
    sg = jax.lax.all_gather(s2[0], axis_name)
    out = dequantize(qg, sg.reshape(n, 1)).reshape(-1)
    out = out[:x.size] if pad else out
    return out.reshape(x.shape).astype(x.dtype)


def make_compressed_allreduce(mesh, dp_axes=("data",)):
    """jit-able f32->int8-wire all-reduce over the data axes via shard_map."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in dp_axes:
        n *= sizes[a]
    axis = dp_axes[0] if len(dp_axes) == 1 else dp_axes

    def ar(x):
        def inner(xs):
            return compressed_psum(xs, axis, n)
        return jax.shard_map(inner, mesh=mesh, in_specs=P(),
                             out_specs=P(), axis_names=set(dp_axes),
                             check_vma=False)(x)

    return ar
