"""Substrate tests: data pipeline, checkpoint/restore+reshard, trainer
fault-tolerance (restart, elastic, straggler watchdog), serving runtime,
lease-coherent KV cache, lease-sync local SGD, gradient compression."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs as cfgs
from repro.checkpoint.manager import CheckpointManager
from repro.coherence.kv_lease import AuthoritativeStore, LeaseKVCache
from repro.coherence.lease_sync import LeaseConfig, VmappedWorkers
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import auto_mesh
from repro.optim import adamw
from repro.optim.compress import dequantize, ef_compress, quantize
from repro.runtime.server import Request, Server
from repro.runtime.trainer import Trainer, TrainerConfig


SMOKE = cfgs.SMOKE["smollm-360m"]


def tiny_data(cfg, B=2, S=32):
    return SyntheticLM(cfg, DataConfig(global_batch=B, seq_len=S))


# ------------------------------------------------------------------ data
def test_data_deterministic_and_shardable():
    d1 = tiny_data(SMOKE)
    d2 = tiny_data(SMOKE)
    b1, b2 = d1.batch(7), d2.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (2, 32)
    assert (d1.batch(8)["tokens"] != b1["tokens"]).any()
    # host slicing partitions the global batch
    g = SyntheticLM(SMOKE, DataConfig(global_batch=4, seq_len=32))
    h0 = SyntheticLM(SMOKE, DataConfig(global_batch=4, seq_len=32,
                                       host_index=0, host_count=2))
    h1 = SyntheticLM(SMOKE, DataConfig(global_batch=4, seq_len=32,
                                       host_index=1, host_count=2))
    np.testing.assert_array_equal(
        np.concatenate([h0.batch(3)["tokens"], h1.batch(3)["tokens"]]),
        g.batch(3)["tokens"])


# ------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip_and_reshard(tmp_path):
    from repro.models import init_model
    params = init_model(SMOKE, jax.random.PRNGKey(0))
    state = adamw.init_state(params)
    mgr = CheckpointManager(tmp_path, keep=2, async_write=True)
    mgr.save(10, state)
    mgr.save(20, state)
    mgr.save(30, state)
    mgr.wait()
    assert mgr.latest_step() == 30
    # keep=2 garbage-collects the oldest
    assert not (tmp_path / "step_00000010").exists()
    mesh = auto_mesh((1, 1), ("data", "model"))
    from repro.models import model_shardings
    psh = model_shardings(SMOKE, mesh)
    ssh = adamw.state_shardings(psh, mesh)
    got = mgr.restore(None, state, ssh)
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(got.params)[0]),
        np.asarray(jax.tree.leaves(state.params)[0]))


# ---------------------------------------------------------- trainer FT
@pytest.fixture(scope="module")
def micro_trainer_cfg():
    return cfgs.SMOKE["mamba2-130m"]


def test_trainer_checkpoint_restart(tmp_path, micro_trainer_cfg):
    cfg = micro_trainer_cfg
    mesh = auto_mesh((1, 1), ("data", "model"))
    data = tiny_data(cfg)
    t = Trainer(cfg, mesh, tcfg=TrainerConfig(total_steps=8, ckpt_period=4,
                                              ckpt_dir=str(tmp_path)),
                data=data)
    with pytest.raises(RuntimeError, match="simulated node failure"):
        t.run(fail_at=6)
    # restart from step 4 checkpoint and finish
    res = t.resume()
    assert res["final_step"] == 8
    assert any(e["kind"] == "restore" and e["step"] == 4 for e in t.events)
    assert all(np.isfinite(res["losses"]))


def test_trainer_elastic_remesh(tmp_path, micro_trainer_cfg):
    cfg = micro_trainer_cfg
    mesh = auto_mesh((1, 1), ("data", "model"))
    data = tiny_data(cfg)
    t = Trainer(cfg, mesh, tcfg=TrainerConfig(total_steps=6, ckpt_period=3,
                                              ckpt_dir=str(tmp_path)),
                data=data)
    with pytest.raises(RuntimeError):
        t.run(fail_at=4)
    new_mesh = auto_mesh((1, 1), ("data", "model"))  # "smaller" cluster
    res = t.resume(mesh=new_mesh)
    assert res["final_step"] == 6
    assert any(e["kind"] == "elastic_remesh" for e in t.events)


# ------------------------------------------------------------- serving
def test_server_prefix_cache_coherence():
    cfg = SMOKE
    from repro.models import init_model
    params = init_model(cfg, jax.random.PRNGKey(1))
    srv = Server(cfg, params, batch_size=2, max_len=64)
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, cfg.vocab, 16).astype(np.int32)
    reqs = [Request(rid=i, prompt=prompt, max_new=4) for i in range(4)]
    out = srv.serve(reqs)
    assert set(out) == {0, 1, 2, 3}
    # the call's identical groups share ONE batched probe + one prefill;
    # a repeated serve is a lease hit (no second prefill write-through)
    out2 = srv.serve(reqs)
    assert srv.cache_stats["hits"] >= 1
    np.testing.assert_array_equal(out[0], out[2])
    np.testing.assert_array_equal(out[0], out2[0])


def test_lease_kv_cache_protocol_semantics():
    store = AuthoritativeStore(rd_lease=8, wr_lease=4)
    r1 = LeaseKVCache(store)
    r2 = LeaseKVCache(store)
    r1.put("p", "v1")
    assert r2.get("p")[0] == "v1"              # compulsory fetch
    assert r2.get("p")[0] == "v1"              # lease hit
    assert r2.stats["hits"] == 1
    r1.put("p", "v2")                          # writer updates; NO inval msg
    got = r2.get("p")[0]
    assert got in ("v1", "v2")                 # weakly consistent window
    r2.cts = store.blocks["p"].memts + 1       # reader syncs (fence)
    assert r2.get("p")[0] == "v2"              # lease expired -> coherent
    assert r2.stats["coherence_misses"] >= 1


# ----------------------------------------------------- lease local-SGD
def test_lease_sync_w1_equals_sync_dp():
    cfg = cfgs.SMOKE["smollm-360m"]
    data = tiny_data(cfg)
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    w = VmappedWorkers(cfg, opt, LeaseConfig(wr_lease=1), n_workers=2,
                       key=jax.random.PRNGKey(0))
    mk = lambda s: {"tokens": np.stack([data.batch(s)["tokens"][0],
                                        data.batch(s)["tokens"][1]])[:, None][:, 0][None].repeat(2, 0)[..., :32]}
    # simpler: two workers, two different single-row batches
    for s in range(2):
        b = data.batch(s)["tokens"]
        batches = {"tokens": np.stack([b[0:1], b[1:2]])}
        w.step(batches)
    p = jax.tree.leaves(w.state.params)[0]
    np.testing.assert_allclose(np.asarray(p[0]), np.asarray(p[1]),
                               rtol=1e-5, atol=1e-6)


def test_lease_sync_reduces_collective_bytes():
    cfg = cfgs.SMOKE["smollm-360m"]
    data = tiny_data(cfg)
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=20)
    key = jax.random.PRNGKey(0)
    w1 = VmappedWorkers(cfg, opt, LeaseConfig(wr_lease=1), 2, key)
    w4 = VmappedWorkers(cfg, opt, LeaseConfig(wr_lease=4), 2, key)
    for s in range(8):
        b = data.batch(s)["tokens"]
        batches = {"tokens": np.stack([b[0:1], b[1:2]])}
        l1 = w1.step(batches)
        l4 = w4.step(batches)
    assert w4.collective_bytes * 3 < w1.collective_bytes
    assert np.isfinite(l1) and np.isfinite(l4)
    # after the final sync both replicas agree (write-through invariant)
    p = jax.tree.leaves(w4.state.params)[0]
    np.testing.assert_allclose(np.asarray(p[0]), np.asarray(p[1]),
                               rtol=1e-5, atol=1e-6)
    assert w4.clock.memts > 0                      # Lamport clock advanced


# ---------------------------------------------------------- compression
def test_quantize_roundtrip_bounded_error():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((128, 64)).astype(np.float32)
    q, s = quantize(jnp.asarray(x))
    err = np.abs(dequantize(q, s) - x)
    assert err.max() <= float(np.abs(x).max()) / 127.0 + 1e-6


def test_error_feedback_accumulates_to_unbiased():
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal(1024).astype(np.float32)) * 1e-3
    err = jnp.zeros_like(g)
    total_sent = jnp.zeros_like(g)
    for _ in range(50):
        sent, err = ef_compress(g, err)
        total_sent = total_sent + sent
    # long-run average of transmitted gradient matches the true gradient
    np.testing.assert_allclose(np.asarray(total_sent / 50), np.asarray(g),
                               atol=float(jnp.abs(g).max()) / 40)
