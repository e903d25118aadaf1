"""shard_map MoE dispatch == the GSPMD dispatch math, judged against the
unsharded reference (8 fake devices, subprocess so the device-count flag
lands before jax init).

The comparison anchor is `_moe_gspmd` run WITHOUT a mesh: on this
container's jax 0.4.x, the GSPMD partitioner miscompiles the global-scatter
dispatch on a mixed (data x model) mesh (outputs off by ~40% of their
magnitude vs. the same math unsharded — see DESIGN.md §4), so comparing the
two mesh paths to each other would test the partitioner bug, not the
dispatch.  The shard_map path with explicit collectives is exact.
"""
import subprocess
import sys

SCRIPT = r'''
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, "src")
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs as cfgs
from repro.models import moe as moe_mod
from repro.sharding import ShardCtx, NOSHARD
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2, 4), ("data", "model"))
ctx = ShardCtx(mesh)
cfg = dataclasses.replace(cfgs.SMOKE["deepseek-v2-236b"], n_experts=8,
                          top_k=2, capacity_factor=8.0)  # no drops => equal
spec = moe_mod.moe_spec(cfg)
from repro.models.params import materialize
p = materialize(spec, jax.random.PRNGKey(0))
h = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
ref, aref = jax.jit(lambda p, h: moe_mod._moe_gspmd(cfg, p, h, NOSHARD))(p, h)
o2, a2 = jax.jit(lambda p, h: moe_mod._moe_shard_map(cfg, p, h, ctx))(p, h)
np.testing.assert_allclose(np.asarray(ref), np.asarray(o2), rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(float(aref), float(a2), rtol=0.3)  # aux: local approx
# single-mesh-axis GSPMD runs are NOT hit by the partitioner bug; pin that
mesh1 = auto_mesh((1, 8), ("data", "model"))
o1, a1 = jax.jit(lambda p, h: moe_mod._moe_gspmd(cfg, p, h, ShardCtx(mesh1)))(p, h)
np.testing.assert_allclose(np.asarray(ref), np.asarray(o1), rtol=2e-4, atol=2e-4)
print("MOE_MATCH_OK")
'''


def test_moe_shardmap_matches_gspmd():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=520, cwd=".")
    assert "MOE_MATCH_OK" in r.stdout, r.stdout + r.stderr
