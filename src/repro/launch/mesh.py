"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the dry-run's 512 placeholder
devices to work while smoke tests/benches still see 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model and training
    code is written for GSPMD sharding propagation, and ``make_mesh``
    otherwise makes the axes Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data","model").
    Multi-pod: 2x16x16 = 512 chips ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (CPU smoke tests / examples): 1 device mesh."""
    n = len(jax.devices())
    return auto_mesh((n, 1), ("data", "model"))


def make_fabric_mesh(n_shards=None, devices=None):
    """The coherence fabric's 1-axis ``fabric`` mesh: TSU shard ``s`` lives
    on device ``s // (n_shards / D)`` (the paper's one-TSU-per-HBM-stack
    placement; see coherence/fabric/arrays.ShardedArrayFabric).

    Uses the LARGEST device count that divides ``n_shards`` so every
    device owns an equal contiguous run of shards; on a 1-device host this
    degenerates to a single-device mesh (same shard_map entry point)."""
    import numpy as np
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else list(jax.devices())
    d = len(devs)
    if n_shards is not None:
        while d > 1 and n_shards % d:
            d -= 1
    return Mesh(np.array(devs[:d]), ("fabric",))
