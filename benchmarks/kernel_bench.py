"""Pallas kernel microbench: interpret-mode on CPU validates + times the
reference XLA path (us/call).  On a TPU the same kernels compile with
Mosaic (``kernels.lanes.interpret_mode``); derived column reports the
modelled VMEM-resident HBM-traffic advantage vs the unfused jnp path."""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.kernels import ref


def _time(f, *args, iters=5):
    f(*args).block_until_ready() if hasattr(f(*args), "block_until_ready") \
        else None
    t0 = time.time()
    for _ in range(iters):
        out = f(*args)
        jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def main(force=False):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    # flash attention: ref path timing + kernel HBM-traffic model
    B, S, H, D = 2, 1024, 8, 64
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)
    fa_ref = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v, causal=True))
    us = _time(fa_ref, q, k, v)
    qkv = 4 * B * S * H * D * 2
    scores = B * H * S * S * 4 * 2              # materialized fwd (w+r)
    emit("kernel/flash_attention", us,
         f"hbm_bytes_kernel={qkv};hbm_bytes_xla={qkv + scores};"
         f"saving={(qkv + scores)/qkv:.1f}x")
    # decode attention
    kc = jax.random.normal(ks[1], (B, 8192, H, D), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (B, 8192, H, D), jnp.bfloat16)
    q1 = q[:, :1]
    da_ref = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v, causal=False,
                                                       kv_len=8000))
    emit("kernel/decode_attention", _time(da_ref, q1, kc, vc),
         "streams_kv_once=True")
    # rmsnorm
    x = jax.random.normal(ks[0], (4096, 1024), jnp.bfloat16)
    w = jax.random.normal(ks[1], (1024,), jnp.float32) * 0.1
    rn = jax.jit(lambda x, w: ref.rmsnorm_ref(x, w))
    emit("kernel/rmsnorm", _time(rn, x, w), "fused_reads=1_vs_3")
    # ssd chunk
    import numpy as np
    Bc, nc, Q, Hh, P, N = 1, 4, 64, 4, 32, 32
    xs = jax.random.normal(ks[0], (Bc, nc, Q, Hh, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bc, nc, Q, Hh)))
    A = -jnp.exp(jax.random.uniform(ks[2], (Hh,)))
    Bm = jax.random.normal(ks[1], (Bc, nc, Q, Hh, N))
    Cm = jax.random.normal(ks[2], (Bc, nc, Q, Hh, N))
    from repro.kernels.ssd_chunk import ssd_chunk
    f = lambda: ssd_chunk(xs, dt, A, Bm, Cm, interpret=True)
    t0 = time.time(); jax.block_until_ready(f()); us0 = (time.time()-t0)*1e6
    emit("kernel/ssd_chunk_interpret", us0, "intra_chunk_vmem_resident=True")
    # lease probe
    from repro.kernels.lease_probe import lease_probe
    tags = jnp.asarray(np.random.randint(-1, 50, (1024, 16)), jnp.int32)
    rts = jnp.asarray(np.random.randint(0, 40, (1024, 16)), jnp.int32)
    vec = lambda: jnp.asarray(np.random.randint(0, 40, 1024), jnp.int32)
    t0 = time.time()
    jax.block_until_ready(lease_probe(tags, rts, vec(), vec(), vec(), vec(),
                                      interpret=True))
    emit("kernel/lease_probe_interpret", (time.time()-t0)*1e6,
         "protocol_hot_loop=fused")
    # fused miss/write-pass round kernels (ISSUE 8): steady-state us/call
    # vs the unfused path (2 lease_probe launches + jnp grant/install ops)
    from repro.kernels.tier_pass import miss_round, write_grant
    M, W1, W2, C = 512, 4, 16, 64
    r = lambda lo, hi, *shp: jnp.asarray(
        np.random.randint(lo, hi, shp), jnp.int32)
    miss_in = (r(-1, 50, M, W1), r(0, 40, M, W1), r(-1, 50, M, W2),
               r(0, 40, M, W2), r(0, 40, M, W2), r(-1, 50, M, C),
               r(0, 60000, M, C), r(0, 40, M), r(0, 40, M), r(0, 50, M),
               r(0, 2, M), jnp.full((M,), 10, jnp.int32))
    emit("kernel/miss_round_interpret",
         _time(lambda *a: miss_round(*a, interpret=True), *miss_in),
         f"lanes={M};fuses=3_probes+grant+2_installs")

    def unfused(*a):
        out = ref.miss_round_ref(*a)
        p1 = lease_probe(a[0], a[1], a[7], a[9], a[7], a[7], interpret=True)
        p2 = lease_probe(a[2], a[3], a[8], a[9], a[8], a[8], interpret=True)
        return out, p1, p2
    emit("kernel/miss_round_unfused",
         _time(unfused, *miss_in), "oracle+2_lease_probe_launches")
    wg_in = (r(-1, 50, M, C), r(0, 60000, M, C), r(0, 99, M, C),
             r(0, 50, M), jnp.full((M,), 5, jnp.int32))
    emit("kernel/write_grant_interpret",
         _time(lambda *a: write_grant(*a, interpret=True), *wg_in),
         f"lanes={M};fuses=probe+lex_victim+mm_write")


if __name__ == "__main__":
    main()
