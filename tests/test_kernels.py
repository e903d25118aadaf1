"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, swept
over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lease_probe import lease_probe
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_chunk import ssd_chunk
from repro.models.ssm import ssd_chunked


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,Sq,Sk,Hq,Hkv,D", [
    (1, 128, 128, 4, 4, 64),
    (2, 256, 256, 8, 2, 64),      # GQA 4:1
    (1, 128, 384, 4, 1, 128),     # MQA, rectangular
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
def test_flash_attention(B, Sq, Sk, Hq, Hkv, D, dtype, causal, window):
    if causal and Sq != Sk:
        pytest.skip("causal assumes aligned q/k")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=64, bk=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,Sk,Hq,Hkv,D,kv_len", [
    (2, 512, 4, 4, 64, 384),
    (1, 1024, 8, 2, 128, 1000),
    (4, 256, 4, 1, 64, 1),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, Sk, Hq, Hkv, D, kv_len, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), dtype)
    out = decode_attention(q, k, v, kv_len, bk=128, interpret=True)
    want = ref.attention_ref(q, k, v, causal=False, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("R,D", [(64, 256), (128, 960), (32, 80)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(R, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(ks[0], (R, D), dtype)
    w = jax.random.normal(ks[1], (D,), jnp.float32) * 0.1
    out = rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,nc,Q,H,P,N", [
    (1, 2, 32, 2, 16, 16),
    (2, 4, 64, 4, 32, 32),
])
def test_ssd_chunk_kernel(B, nc, Q, H, P, N):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, nc, Q, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, nc, Q, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=1.5))
    Bc = jax.random.normal(ks[3], (B, nc, Q, H, N), jnp.float32)
    Cc = jax.random.normal(ks[4], (B, nc, Q, H, N), jnp.float32)
    y, st, cum = ssd_chunk(x, dt, A, Bc, Cc, interpret=True)
    for b in range(B):
        for c in range(nc):
            for h in range(H):
                yr, sr, cr = ref.ssd_chunk_ref(x[b, c, :, h], dt[b, c, :, h],
                                               A[h], Bc[b, c, :, h],
                                               Cc[b, c, :, h])
                np.testing.assert_allclose(y[b, c, :, h], yr, rtol=1e-4,
                                           atol=1e-4)
                np.testing.assert_allclose(st[b, c, h], sr, rtol=1e-4,
                                           atol=1e-4)
                np.testing.assert_allclose(cum[b, c, :, h], cr, rtol=1e-5,
                                           atol=1e-5)


def test_ssd_kernel_matches_full_ssm_path():
    """Kernel intra-chunk + jnp inter-chunk == models.ssm.ssd_chunked."""
    B, S, H, P, N, Q = 2, 128, 4, 16, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=1.5))
    Bc = jax.random.normal(ks[3], (B, S, 1, N), jnp.float32)
    Cc = jax.random.normal(ks[4], (B, S, 1, N), jnp.float32)
    y_ref, final_ref = ssd_chunked(x, dt, A, Bc, Cc, Q)

    nc = S // Q
    xc = x.reshape(B, nc, Q, H, P)
    dtc = dt.reshape(B, nc, Q, H)
    Bh = jnp.broadcast_to(Bc.reshape(B, nc, Q, 1, N), (B, nc, Q, H, N))
    Ch = jnp.broadcast_to(Cc.reshape(B, nc, Q, 1, N), (B, nc, Q, H, N))
    y_in, st, cum = ssd_chunk(xc, dtc, A, Bh, Ch, interpret=True)
    # inter-chunk combine (jnp)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                    # [B,nc,H]
    state = jnp.zeros((B, H, N, P))
    ys = []
    for c in range(nc):
        decay_in = jnp.exp(cum[:, c])                          # [B,Q,H]
        y_int = jnp.einsum("bqhn,bhnp->bqhp",
                           Ch[:, c] * decay_in.transpose(0, 1, 2)[..., None],
                           state)
        ys.append(y_in[:, c] + y_int)
        state = state * chunk_decay[:, c][:, :, None, None] + st[:, c]
    y = jnp.stack(ys, 1).reshape(B, S, H, P)
    np.testing.assert_allclose(y, y_ref, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(state, final_ref, rtol=1e-3, atol=1e-3)


def _lease_probe_inputs(N, W, seed=0):
    rng = np.random.default_rng(seed)
    tag_rows = rng.integers(-1, 50, (N, W)).astype(np.int32)
    rts_rows = rng.integers(0, 40, (N, W)).astype(np.int32)
    cts = rng.integers(0, 40, (N,)).astype(np.int32)
    addr = rng.integers(0, 50, (N,)).astype(np.int32)
    mwts = rng.integers(0, 40, (N,)).astype(np.int32)
    mrts = mwts + rng.integers(1, 10, (N,)).astype(np.int32)
    # make hit ways unique per row (engine invariant: one copy per cache)
    for i in range(N):
        seen = set()
        for j in range(W):
            if tag_rows[i, j] in seen:
                tag_rows[i, j] = -2 - j
            seen.add(tag_rows[i, j])
    return tag_rows, rts_rows, cts, addr, mwts, mrts


_PROBE_OUTS = ["tag_hit", "hit", "way", "row_rts", "nwts", "nrts", "ncts"]


@pytest.mark.parametrize("N,W", [(64, 4), (256, 16), (100, 8)])
def test_lease_probe(N, W):
    tag_rows, rts_rows, cts, addr, mwts, mrts = _lease_probe_inputs(N, W)
    got = lease_probe(jnp.asarray(tag_rows), jnp.asarray(rts_rows),
                      jnp.asarray(cts), jnp.asarray(addr),
                      jnp.asarray(mwts), jnp.asarray(mrts), interpret=True)
    want = ref.lease_probe_ref(tag_rows, rts_rows, cts, addr, mwts, mrts)
    for g, w, name in zip(got, want, _PROBE_OUTS):
        g, w = np.asarray(g), np.asarray(w)
        if name == "way":           # way only meaningful on tag hits
            eq = (tag_rows == addr[:, None]).any(-1)
            np.testing.assert_array_equal(g[eq], w[eq], err_msg=name)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


def test_lease_probe_duplicate_tags_use_first_way():
    """The engine can hold a stale duplicate of a tag (coherence-miss
    installs go to a victim way while the expired copy stays live): the
    probe must read the FIRST matching way, exactly like argmax/ref —
    not mix the ways' timestamps."""
    tag_rows = np.array([[7, 7, -1, -1],
                         [7, -1, 7, -1],
                         [3, 7, 7, 7]], np.int32)
    rts_rows = np.array([[5, 20, 0, 0],
                         [20, 0, 5, 0],
                         [9, 2, 30, 40]], np.int32)
    cts = np.array([10, 10, 10], np.int32)
    addr = np.array([7, 7, 7], np.int32)
    mwts = np.zeros(3, np.int32)
    mrts = np.full(3, 12, np.int32)
    got = lease_probe(*map(jnp.asarray, (tag_rows, rts_rows, cts, addr,
                                         mwts, mrts)), interpret=True)
    want = ref.lease_probe_ref(tag_rows, rts_rows, cts, addr, mwts, mrts)
    for g, w, name in zip(got, want, _PROBE_OUTS):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    # row 0: first way rts=5 < cts -> lease-expired despite the rts=20 dup
    np.testing.assert_array_equal(np.asarray(got[1]), [False, True, False])


# compiled (Mosaic) cases of the lease kernels: tests/test_tpu_compile.py
@pytest.mark.parametrize("interpret", [True])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lease_probe_matches_protocol(interpret, seed):
    """Bit-for-bit parity of the kernel's install math against
    core.protocol (Algorithms 1-5) on randomized tag/rts/cts batches —
    the engine's hot path is pinned to the protocol's decision surface."""
    from repro.core import protocol
    tag_rows, rts_rows, cts, addr, mwts, mrts = \
        _lease_probe_inputs(192, 8, seed)
    got = lease_probe(jnp.asarray(tag_rows), jnp.asarray(rts_rows),
                      jnp.asarray(cts), jnp.asarray(addr),
                      jnp.asarray(mwts), jnp.asarray(mrts),
                      interpret=interpret)
    tag_hit, hit, way, row_rts, nwts, nrts, ncts = map(np.asarray, got)
    lease = protocol.install(jnp.asarray(cts), jnp.asarray(mwts),
                             jnp.asarray(mrts))
    np.testing.assert_array_equal(nwts, np.asarray(lease.wts))
    np.testing.assert_array_equal(nrts, np.asarray(lease.rts))
    np.testing.assert_array_equal(
        ncts, np.asarray(protocol.cts_after_write(jnp.asarray(cts),
                                                  lease.wts)))
    # validity: hit == tag match AND protocol.valid(cts, rts of the way)
    eq = tag_rows == addr[:, None]
    want_tag_hit = eq.any(-1)
    rts_way = np.where(want_tag_hit,
                       np.take_along_axis(rts_rows, eq.argmax(-1)[:, None],
                                          1)[:, 0], 0)
    np.testing.assert_array_equal(tag_hit, want_tag_hit)
    np.testing.assert_array_equal(
        hit, want_tag_hit & np.asarray(protocol.valid(cts, rts_way)))
    np.testing.assert_array_equal(row_rts, rts_way)


# ------------------------------------------------ fused miss/write rounds
def _miss_round_inputs(N, W1, W2, C, seed=0):
    rng = np.random.default_rng(seed)
    r = lambda lo, hi, shp: rng.integers(lo, hi, shp).astype(np.int32)
    return (r(-1, 30, (N, W1)), r(0, 40, (N, W1)), r(-1, 30, (N, W2)),
            r(0, 40, (N, W2)), r(0, 40, (N, W2)), r(-1, 30, (N, C)),
            r(0, 70000, (N, C)), r(0, 40, N), r(0, 40, N), r(0, 30, N),
            r(0, 2, N), np.full(N, 10, np.int32))


_MISS_OUTS = ["th1", "h1", "way1", "th2", "h2", "way2", "fnd", "tway",
              "mwts", "mrts", "nmem", "ovf", "nwa", "nra", "nw1", "nr1"]
_WAYS = {"way1", "way2", "tway"}           # meaningful only on a tag hit


# compiled (Mosaic) cases of the lease kernels: tests/test_tpu_compile.py
@pytest.mark.parametrize("interpret", [True])
@pytest.mark.parametrize("N,W1,W2,C,seed", [
    (64, 4, 8, 16, 0), (256, 2, 4, 64, 1), (96, 8, 2, 8, 2)])
def test_miss_round_kernel(interpret, N, W1, W2, C, seed):
    """The fused miss-pass round kernel (3 probes + Algorithm 3 read
    grant + both Algorithm 1/2 install levels) is bit-identical to the
    protocol-derived oracle, interpret and compiled."""
    from repro.kernels.tier_pass import miss_round
    ins = _miss_round_inputs(N, W1, W2, C, seed)
    got = miss_round(*map(jnp.asarray, ins), interpret=interpret)
    want = ref.miss_round_ref(*map(jnp.asarray, ins))
    tags = {"way1": ins[0], "way2": ins[2], "tway": ins[5]}
    for g, w, name in zip(got, want, _MISS_OUTS):
        g, w = np.asarray(g), np.asarray(w)
        if name in _WAYS:
            eq = (tags[name] == ins[9][:, None]).any(-1)
            np.testing.assert_array_equal(g[eq], w[eq], err_msg=name)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_miss_round_matches_state_rules(seed):
    """Pin the fused kernel's grant + install math to core.state /
    core.protocol: the TSU read grant equals ``state.tsu_lease`` and the
    two install levels equal chained ``state.install_lease`` calls, on
    lanes where the kernel's masks make them observable."""
    from repro.core import state as S
    from repro.kernels.tier_pass import miss_round
    N = 128
    ins = _miss_round_inputs(N, 4, 4, 32, seed)
    (th1, h1, way1, th2, h2, way2, fnd, tway, mwts, mrts, nmem, ovf,
     nwa, nra, nw1, nr1) = miss_round(*map(jnp.asarray, ins),
                                      interpret=True)
    cts1, cts2, addr, act, rd = (jnp.asarray(x) for x in ins[7:])
    # TSU grant: entry clock is the first-match row value (0 if absent)
    eqt = jnp.asarray(ins[5]) == addr[:, None]
    first = eqt & (jnp.cumsum(eqt.astype(jnp.int32), -1) == 1)
    memts = jnp.where(eqt.any(-1),
                      jnp.sum(jnp.where(first, jnp.asarray(ins[6]), 0), -1),
                      0)
    gr = S.tsu_lease(memts, jnp.zeros(memts.shape, bool), rd, rd)
    np.testing.assert_array_equal(np.asarray(mwts), np.asarray(gr.wts))
    np.testing.assert_array_equal(np.asarray(mrts), np.asarray(gr.rts))
    np.testing.assert_array_equal(np.asarray(nmem), np.asarray(gr.new_memts))
    # install chain: shared level then replica level
    wA, rA, _ = S.install_lease(cts2, mwts, mrts)
    np.testing.assert_array_equal(np.asarray(nwa), np.asarray(wA))
    np.testing.assert_array_equal(np.asarray(nra), np.asarray(rA))
    rwts = jnp.where(h2, ref._first_match_ref(
        jnp.asarray(ins[2]) == addr[:, None], jnp.asarray(ins[4])), nwa)
    rrts = jnp.where(h2, ref._first_match_ref(
        jnp.asarray(ins[2]) == addr[:, None], jnp.asarray(ins[3])), nra)
    w1, r1, _ = S.install_lease(cts1, rwts, rrts)
    np.testing.assert_array_equal(np.asarray(nw1), np.asarray(w1))
    np.testing.assert_array_equal(np.asarray(nr1), np.asarray(r1))
    # mask algebra: the kernel's flags obey the round body's lattice
    th1, h1, th2, h2, fnd = map(np.asarray, (th1, h1, th2, h2, fnd))
    assert not (h1 & ~th1).any() and not (h2 & ~th2).any()
    assert not (th1 & ~np.asarray(act).astype(bool)).any()
    assert not (th2 & np.asarray(h1)).any()
    assert not (fnd & np.asarray(h2)).any()


# compiled (Mosaic) cases of the lease kernels: tests/test_tpu_compile.py
@pytest.mark.parametrize("interpret", [True])
@pytest.mark.parametrize("N,C,seed", [(64, 16, 0), (256, 64, 1), (40, 8, 2)])
def test_write_grant_kernel(interpret, N, C, seed):
    """The fused write-side TSU kernel (probe + lexicographic victim +
    mm_write grant) is bit-identical to the oracle and to
    ``state.victim_lex``/``state.tsu_lease``, interpret and compiled."""
    from repro.core import state as S
    from repro.kernels.tier_pass import write_grant
    rng = np.random.default_rng(seed)
    ts_tag = rng.integers(-1, 20, (N, C)).astype(np.int32)
    ts_mem = rng.integers(0, 70000, (N, C)).astype(np.int32)
    ts_seq = rng.integers(0, 50, (N, C)).astype(np.int32)
    addr = rng.integers(0, 20, N).astype(np.int32)
    wl = rng.integers(1, 10, N).astype(np.int32)
    got = write_grant(*map(jnp.asarray, (ts_tag, ts_mem, ts_seq, addr, wl)),
                      interpret=interpret)
    want = ref.write_grant_ref(*map(jnp.asarray,
                                    (ts_tag, ts_mem, ts_seq, addr, wl)))
    for g, w, name in zip(got, want,
                          ["th", "way", "full", "wts", "rts", "nmem",
                           "ovf"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    # pin the victim rule to state.victim_lex on the miss lanes
    th, way = got[0], got[1]
    pad = lambda a: jnp.concatenate(
        [jnp.asarray(a)[:, None, :], jnp.zeros((N, 1, 1), jnp.int32)], -1)
    vic = S.victim_lex(pad(ts_tag), pad(ts_mem), pad(ts_seq),
                       jnp.arange(N), jnp.zeros(N, jnp.int32))
    np.testing.assert_array_equal(np.asarray(way)[~np.asarray(th)],
                                  np.asarray(vic)[~np.asarray(th)])
