"""Token-choice top-k MoE with capacity-based scatter dispatch.

Dispatch avoids the classic [T, E, C] one-hot (O(T*E*C) memory): we compute each
token's position-in-expert with a cumsum over a [T*k, E] int32 one-hot, then
scatter token embeddings into an [E*C, D] buffer.  Experts are sharded over the
"model" mesh axis (expert parallelism); capacity over "data".  GSPMD inserts the
dispatch collectives — replaced by explicit all_to_all in the §Perf hillclimb.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import swiglu
from repro.models.params import P
from repro.sharding import NOSHARD


def moe_spec(cfg: ModelConfig) -> dict:
    D, E = cfg.d_model, cfg.n_experts
    F = cfg.d_ff_expert or cfg.d_ff
    s = {
        "router": P((D, E), ("embed", None)),
        "wg": P((E, D, F), ("experts", "embed", "expert_mlp")),
        "wi": P((E, D, F), ("experts", "embed", "expert_mlp")),
        "wo": P((E, F, D), ("experts", "expert_mlp", "embed")),
    }
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        s["shared"] = {
            "wg": P((D, Fs), ("embed", "mlp")),
            "wi": P((D, Fs), ("embed", "mlp")),
            "wo": P((Fs, D), ("mlp", "embed")),
        }
    return s


def capacity_for(cfg: ModelConfig, n_tokens: int) -> int:
    c = math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, (c + 7) // 8 * 8)


def moe_apply(cfg: ModelConfig, p: dict, h, ctx=NOSHARD):
    """h: [B, S, D] -> (out [B, S, D], aux_loss scalar).

    Two dispatch paths:
      * shard_map (default on a mesh with a "model" axis): per-device local
        scatter + ONE all_to_all over the expert-parallel axis + local expert
        compute.  Wire bytes per layer ~ 4x the dispatch buffer.
      * GSPMD global-scatter fallback: correct everywhere (CPU smoke tests),
        but the partitioner lowers the global scatter to a partial-buffer
        all-reduce PER LAYER (~20 GB x 59 layers x 3 passes on deepseek-v2 —
        the §Perf Pair-A baseline pathology).
    """
    if (ctx.mesh is not None and cfg.moe_shard_map
            and "model" in ctx.mesh.axis_names
            and cfg.n_experts % dict(zip(ctx.mesh.axis_names,
                                         ctx.mesh.devices.shape))["model"] == 0):
        return _moe_shard_map(cfg, p, h, ctx)
    return _moe_gspmd(cfg, p, h, ctx)


def _moe_gspmd(cfg: ModelConfig, p: dict, h, ctx=NOSHARD):
    B, S, D = h.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    C = capacity_for(cfg, T)
    cd = h.dtype
    x = h.reshape(T, D)

    x = ctx.constrain(x, "tokens", None)
    logits = (x @ p["router"].astype(cd)).astype(jnp.float32)      # [T, E]
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)                           # [T, k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux loss
    f_e = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(f_e * gates.mean(0))

    fe = ctx.constrain(topi.reshape(T * k), "tokens")              # flat experts
    onehot = (fe[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    onehot = ctx.constrain(onehot, "tokens", None)
    pos_all = jnp.cumsum(onehot, axis=0) - 1                       # [T*k, E]
    pos_all = ctx.constrain(pos_all, "tokens", None)
    mypos = jnp.take_along_axis(pos_all, fe[:, None], axis=1)[:, 0]
    keep = mypos < C
    dest = jnp.where(keep, fe * C + mypos, E * C)                  # drop row E*C

    x_rep = ctx.constrain(jnp.repeat(x, k, axis=0), "tokens", None)  # [T*k, D]
    buf = jnp.zeros((E * C + 1, D), cd).at[dest].set(x_rep, mode="drop")
    xe = ctx.constrain(buf[: E * C].reshape(E, C, D),
                       "experts", "capacity", None)

    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(cd))
    u = jnp.einsum("ecd,edf->ecf", xe, p["wi"].astype(cd))
    act = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * u
    ye = jnp.einsum("ecf,efd->ecd", act, p["wo"].astype(cd))
    ye = ctx.constrain(ye, "experts", "capacity", None)

    y_pad = jnp.concatenate([ye.reshape(E * C, D),
                             jnp.zeros((1, D), cd)], axis=0)
    y_tok = y_pad[dest] * (keep[:, None] * topv.reshape(T * k)[:, None]).astype(cd)
    out = y_tok.reshape(T, k, D).sum(axis=1)

    if cfg.n_shared_experts:
        sp = p["shared"]
        out = out + swiglu(x, sp["wg"], sp["wi"], sp["wo"], cd)
    return out.reshape(B, S, D), aux


# -------------------------------------------------- shard_map dispatch path
def _moe_shard_map(cfg: ModelConfig, p: dict, h, ctx):
    """Expert parallelism with explicit collectives (the §Perf fix).

    Layout: tokens manual over (pod,data,model); experts over "model"; expert
    weights FSDP-gathered (bf16) inside; ONE all_to_all each way over "model".
    shard_map's transpose turns the weight all_gathers into reduce-scatters
    for the gradients — no per-layer gradient all-reduce.
    """
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp_axes:
        n_dp *= sizes[a]
    n_dev = n_dp * m
    B, S, D = h.shape
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // m
    cd = h.dtype
    T = B * S
    if T % n_dev or S % m or B % n_dp:
        return _moe_gspmd(cfg, p, h, ctx)
    t_loc = T // n_dev
    C = capacity_for(cfg, t_loc)                       # per-device capacity

    from repro.sharding import partition_spec as pspec_of
    wg_spec = pspec_of(mesh, p["wg"].shape, ("experts", "embed", "expert_mlp"))
    wo_spec = pspec_of(mesh, p["wo"].shape, ("experts", "expert_mlp", "embed"))
    r_spec = pspec_of(mesh, p["router"].shape, ("embed", None))
    def _axes_of(spec, dim):
        if len(spec) <= dim or spec[dim] is None:
            return ()
        e = spec[dim]
        return e if isinstance(e, tuple) else (e,)

    gather_axes = _axes_of(wg_spec, 1)
    router_axes = _axes_of(r_spec, 0)

    def local(x, router, wg, wi, wo):
        # x: [B_loc, S_loc, D]; weights: local shards
        xf = x.reshape(-1, D)                          # [t_loc, D]
        if router_axes:
            router = jax.lax.all_gather(router, router_axes, axis=0,
                                        tiled=True)
        if gather_axes:
            wg = jax.lax.all_gather(wg, gather_axes, axis=1, tiled=True)
            wi = jax.lax.all_gather(wi, gather_axes, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, gather_axes, axis=2, tiled=True)
        logits = (xf @ router.astype(cd)).astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(gates, k)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        f_e = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(
            1.0) / (t_loc * k)
        aux = E * jnp.sum(f_e * gates.mean(0))
        aux = jax.lax.pmean(aux, dp_axes + ("model",))

        fe = topi.reshape(t_loc * k)
        onehot = (fe[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        mypos = jnp.take_along_axis(pos, fe[:, None], axis=1)[:, 0]
        keep = mypos < C
        dest = jnp.where(keep, fe * C + mypos, E * C)
        x_rep = jnp.repeat(xf, k, axis=0)
        buf = jnp.zeros((E * C + 1, D), cd).at[dest].set(x_rep, mode="drop")
        # dispatch: one all_to_all over the expert-parallel axis
        send = buf[: E * C].reshape(m, E_loc * C, D)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=True)  # [m, E_loc*C, D]
        xe = recv.reshape(m, E_loc, C, D).transpose(1, 0, 2, 3) \
                 .reshape(E_loc, m * C, D)
        g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(cd))
        u = jnp.einsum("ecd,edf->ecf", xe, wi.astype(cd))
        act = jax.nn.silu(g.astype(jnp.float32)).astype(cd) * u
        ye = jnp.einsum("ecf,efd->ecd", act, wo.astype(cd))
        # inverse all_to_all back to token owners
        back = ye.reshape(E_loc, m, C, D).transpose(1, 0, 2, 3) \
                 .reshape(m, E_loc * C, D)
        mine = jax.lax.all_to_all(back, "model", split_axis=0,
                                  concat_axis=0, tiled=True)
        y_pad = jnp.concatenate([mine.reshape(E * C, D),
                                 jnp.zeros((1, D), cd)], axis=0)
        y_tok = y_pad[dest] * (keep[:, None]
                               * topv.reshape(t_loc * k)[:, None]).astype(cd)
        out = y_tok.reshape(t_loc, k, D).sum(axis=1)
        return out.reshape(x.shape), aux

    x_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0], "model", None)
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, r_spec, wg_spec, wg_spec, wo_spec),
        out_specs=(x_spec, P()),
        axis_names=set(dp_axes) | {"model"}, check_vma=False)(
            h, p["router"], p["wg"], p["wi"], p["wo"])

    if cfg.n_shared_experts:
        sp = p["shared"]
        B_, S_, D_ = h.shape
        out = out + swiglu(h.reshape(-1, D_), sp["wg"], sp["wi"], sp["wo"],
                           cd).reshape(B_, S_, D_)
    return out, aux
