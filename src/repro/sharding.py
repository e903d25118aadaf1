"""Logical-axis sharding rules with divisibility fallback.

Params and activations are annotated with *logical* axis names; this module maps
them onto the physical mesh.  A mesh axis is silently dropped for a tensor dim
whose size is not divisible by the axis size (e.g. smollm's 15 heads on a 16-way
"model" axis, hubert's vocab=504), guaranteeing that every produced
``NamedSharding`` is valid for every architecture in the pool.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# logical axis -> preferred mesh axes (tried in order, greedily combined)
DEFAULT_RULES: dict = {
    "batch": ("pod", "data"),
    "tokens": ("pod", "data"),     # flattened B*S (MoE dispatch)
    "embed": ("pod", "data"),      # ZeRO-3 / FSDP for parameter d_model dims
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "expert_mlp": ("model",),
    "capacity": ("data",),
    "seq": (),                     # unsharded by default
    "seq_shard": ("model",),       # sequence parallelism for residual carries
    "kv_seq": ("data",),           # long-context decode: shard KV length
    "dstate": (),
    "stack": (),                   # scanned layer dim — never sharded
    "fabric_shard": ("fabric",),   # TSU shard-major dims of the coherence
                                   # fabric (launch.mesh.make_fabric_mesh)
    None: (),
}


def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def mesh_axes_for(
    mesh: Mesh,
    dim_size: int,
    logical: Optional[str],
    rules: Optional[dict] = None,
    taken: Optional[set] = None,
) -> Tuple[str, ...]:
    """Greedy: keep prefix of preferred mesh axes while divisibility holds."""
    rules = rules or DEFAULT_RULES
    prefs = rules.get(logical, ())
    out = []
    size = 1
    for ax in prefs:
        if ax not in mesh.axis_names:
            continue
        if taken is not None and ax in taken:
            continue
        nxt = size * _axis_size(mesh, ax)
        if nxt == 0 or dim_size % nxt != 0:
            break
        out.append(ax)
        size = nxt
    return tuple(out)


def partition_spec(
    mesh: Mesh,
    shape: Sequence[int],
    logical_axes: Sequence[Optional[str]],
    rules: Optional[dict] = None,
) -> PartitionSpec:
    """Build a PartitionSpec; each mesh axis used at most once per tensor."""
    assert len(shape) == len(logical_axes), (shape, logical_axes)
    taken: set = set()
    spec = []
    for dim, logical in zip(shape, logical_axes):
        axes = mesh_axes_for(mesh, dim, logical, rules, taken)
        taken.update(axes)
        if not axes:
            spec.append(None)
        elif len(axes) == 1:
            spec.append(axes[0])
        else:
            spec.append(tuple(axes))
    # trim trailing Nones
    while spec and spec[-1] is None:
        spec.pop()
    return PartitionSpec(*spec)


def named_sharding(
    mesh: Mesh,
    shape: Sequence[int],
    logical_axes: Sequence[Optional[str]],
    rules: Optional[dict] = None,
) -> NamedSharding:
    return NamedSharding(mesh, partition_spec(mesh, shape, logical_axes, rules))


class ShardCtx:
    """Threaded through model code; no-ops when mesh is None (CPU smoke tests)."""

    def __init__(self, mesh: Optional[Mesh] = None, rules: Optional[dict] = None):
        self.mesh = mesh
        self.rules = rules or DEFAULT_RULES

    def constrain(self, x, *logical_axes):
        if self.mesh is None:
            return x
        sh = named_sharding(self.mesh, x.shape, logical_axes, self.rules)
        return jax.lax.with_sharding_constraint(x, sh)

    def spec(self, shape, logical_axes) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return named_sharding(self.mesh, shape, logical_axes, self.rules)


NOSHARD = ShardCtx(None)


def rules_without(*axes) -> dict:
    """Rules with given mesh axes removed (e.g. inside a shard_map manual
    region, where constraints may not reference Manual axes)."""
    out = {}
    for k, v in DEFAULT_RULES.items():
        out[k] = tuple(a for a in v if a not in axes)
    return out
