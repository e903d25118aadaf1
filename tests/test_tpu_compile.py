"""The lease kernels compiled by the TPU compiler for a described v5e chip
(no chip attached): the shapes the chip smoke run drives — the op-scan's
single lane, the fast read's widest batch, the miss pass over whole
4096-entry TSU shard rows, the publish storm's write pass and the figure
engine's vmapped probe.  Every case must compile with Mosaic and hold a
``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lease_probe import lease_probe
from repro.kernels.tier_pass import miss_round, write_grant

TSU_CAPACITY = 4096           # chip_smoke.FABRIC's per-shard entries


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs can be cached but never read back
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _probe(*a):
    return lease_probe(*a, interpret=False)


@pytest.mark.parametrize("n,w", [
    (1, 4), (1, 8),               # op-scan probes (replica, shared tier)
    (8, 4), (384, 4), (1000, 4),  # fast read: smallest bucket, uneven sizes
    (1024, 4), (128, 16)])        # fast read's widest batch; engine L2
def test_lease_probe_compiles(one_chip, n, w):
    text = _compiled_text(_probe, one_chip, (n, w), (n, w), (n,), (n,),
                          (n,), (n,))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("w", [4, 16])        # engine L1 / L2 probes
def test_lease_probe_vmapped_engine_form(one_chip, w):
    """``engine.sweep`` vmaps the probe over configs x benchmarks."""
    b, nc = (1, 11), 128
    text = _compiled_text(jax.vmap(jax.vmap(_probe)), one_chip,
                          b + (nc, w), b + (nc, w), *[b + (nc,)] * 4)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [1, 32, 1024])
def test_miss_round_compiles(one_chip, n):
    c = TSU_CAPACITY
    text = _compiled_text(lambda *a: miss_round(*a, interpret=False),
                          one_chip, (n, 4), (n, 4), (n, 8), (n, 8), (n, 8),
                          (n, c), (n, c), *[(n,)] * 5)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [1, 512])
def test_write_grant_compiles(one_chip, n):
    c = TSU_CAPACITY
    text = _compiled_text(lambda *a: write_grant(*a, interpret=False),
                          one_chip, (n, c), (n, c), (n, c), (n,), (n,))
    assert "tpu_custom_call" in text
