"""Flash attention (forward) as a Pallas TPU kernel.

Online-softmax accumulation over KV blocks; grid (B, Hq, nq, nk) with the kv
dimension sequential ("arbitrary") and running (m, l, acc) in VMEM scratch.
GQA: the k/v index maps fold q-heads onto their kv head (h // q_per_kv).
Supports causal and sliding-window masking.  Validated in interpret mode
against ref.attention_ref; on TPU this keeps the [bq, Sk] score tile in VMEM
(never materialized to HBM) — the memory-roofline fix for train/prefill.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, bq, bk, nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)              # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)              # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.zeros_like(s)
    if causal:
        mask = jnp.where(kpos > qpos, NEG_INF, mask)
    if window:
        mask = jnp.where(qpos - kpos >= window, NEG_INF, mask)
    s = s + mask

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                    interpret=None):
    """q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] -> [B, Sq, Hq, D]."""
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    qpk = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    scale = D ** -0.5
    qt = q.transpose(0, 2, 1, 3)                     # [B, Hq, Sq, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, qpk=qpk: (b, h // qpk, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, qpk=qpk: (b, h // qpk, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            _vmem((bq, D), jnp.float32),
            _vmem((bq,), jnp.float32),
            _vmem((bq,), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
