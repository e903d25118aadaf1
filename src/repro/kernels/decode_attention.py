"""Flash-decode: single-token attention over a long KV cache, as a Pallas
kernel.  Grid (B, Hq, nk) with sequential accumulation over KV blocks and
kv_len masking (cache fill level) — the serve_step hot loop for decode_32k /
long_500k.  On TPU the KV cache streams HBM->VMEM once; scores never leave
VMEM."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import interpret_mode

NEG_INF = -1e30


def _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale, bk, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                # [1, D]
    k = k_ref[0, 0].astype(jnp.float32)                # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)
    s = (q @ k.T) * scale                              # [1, bk]
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(kpos >= kvlen_ref[0], NEG_INF, s)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k, v, kv_len, *, bk=512, interpret=None):
    """q: [B,1,Hq,D]; k,v: [B,Sk,Hkv,D]; kv_len: scalar int32."""
    B, Sq, Hq, D = q.shape
    assert Sq == 1
    Sk, Hkv = k.shape[1], k.shape[2]
    qpk = Hq // Hkv
    bk = min(bk, Sk)
    assert Sk % bk == 0
    nk = Sk // bk
    qt = q.transpose(0, 2, 1, 3)                       # [B, Hq, 1, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kernel = functools.partial(_decode_kernel, scale=D ** -0.5, bk=bk, nk=nk)
    from jax.experimental.pallas import tpu as pltpu
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, 1, D), lambda b, h, j, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, j, *_, qpk=qpk: (b, h // qpk, j, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, j, *_, qpk=qpk: (b, h // qpk, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, j, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, D), jnp.float32),
                pltpu.VMEM((1,), jnp.float32),
                pltpu.VMEM((1,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, D), q.dtype),
        interpret=interpret_mode(interpret),
    )(jnp.asarray(kv_len, jnp.int32).reshape(1), qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
