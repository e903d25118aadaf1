"""Lease-synchronized data parallelism — HALCONE's insight applied to
distributed training.

Mapping (DESIGN.md §2b): parameters are the shared cache blocks, each
data-parallel worker is a GPU with logical clock cts = its local step count,
the gradient all-reduce is the write-through, and ``wr_lease`` is the number
of local steps a worker may run on its cached (stale) parameters before the
lease expires and a sync refreshes them.  wr_lease=1 is exact synchronous DP;
wr_lease=W cuts the collective roofline term by ~W at bounded staleness
(local-SGD with Lamport ordering — timestamps from repro.core.protocol).

Two implementations:
  * ``make_lease_window_step`` — shard_map over the "data" axis ("model"
    stays auto-sharded): W local AdamW steps per window, one parameter
    all-reduce at the end.  This is the dry-run / production path.
  * ``VmappedWorkers`` — workers as a leading array axis (vmap), runnable on
    one CPU device; used by tests to check the math (W=1 == sync DP).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import model as M
from repro.optim import adamw
from repro.sharding import ShardCtx


@dataclasses.dataclass(frozen=True)
class LeaseConfig:
    wr_lease: int = 4            # local steps between write-throughs
    rd_lease: int = 4            # eval/readers may be this stale (steps)


class LeaseClock:
    """Lamport bookkeeping for the parameter store (host-level).

    Thin adapter over the coherence fabric: the parameter blob is one block
    in the sharded TSU service, and every window's write-through is an
    authority ``mm_write`` — so training's clock shares the 16-bit overflow
    reinit and the telemetry of the serving path instead of re-deriving the
    rules.  Takes any ``FabricBackend`` (default: the jitted array fabric);
    the legacy host ``TSUFabric`` is still accepted for the oracle tests.
    """

    PARAM_KEY = "params"

    def __init__(self, fabric=None):
        from repro.coherence.fabric import FabricConfig, default_fabric
        self.fabric = fabric if fabric is not None else default_fabric(
            FabricConfig(n_shards=1, max_in_flight=0))

    @property
    def memts(self) -> int:
        return self.fabric.memts(self.PARAM_KEY)

    def on_sync(self, wr_lease: int, version_tag=None):
        from repro.core import protocol
        from repro.coherence.fabric import FabricBackend
        if isinstance(self.fabric, FabricBackend):
            wts, rts, _ = self.fabric.mm_write(self.PARAM_KEY, version_tag,
                                               wr_lease=wr_lease)
            return protocol.Lease(wts, rts)  # the new param version
        grant = self.fabric.write(self.PARAM_KEY, version_tag,
                                  wr_lease=wr_lease)
        return protocol.Lease(grant.wts, grant.rts)


def make_lease_window_step(cfg, mesh, opt: adamw.AdamWConfig,
                           lease: LeaseConfig):
    """Cross-pod lease-synced training (the HALCONE deployment shape).

    Pods play the paper's GPUs: inside a pod, FSDP+TP run synchronously
    (auto axes); ACROSS pods, each pod runs ``wr_lease`` local steps on its
    lease of the parameters, then one write-through (param+moment psum over
    "pod").  Collective traffic across the inter-pod links drops ~W x
    (gradients never cross pods; parameters cross once per window).

    window_step(state, batches): batches leaves [W, B_pod, S] with the global
    batch dim sharded over ("data",) inside each pod.
    """
    from repro.sharding import rules_without
    W = lease.wr_lease
    # inside the manual-over-pod region, constraints may not mention "pod"
    ctx = ShardCtx(mesh, rules=rules_without("pod"))
    assert "pod" in mesh.axis_names, "lease window needs the multi-pod mesh"
    n_pod = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]

    def local_window(state: adamw.TrainState, batches):
        def one(st, batch):
            def lf(params):
                loss, _ = M.loss_fn(cfg, params, batch, ctx)
                return loss

            loss, grads = jax.value_and_grad(lf)(st.params)
            return adamw.apply_updates(opt, st, grads), loss

        state, losses = jax.lax.scan(one, state, batches)
        # write-through at lease expiry: average the diverged pod replicas
        avg = lambda t: jax.tree.map(
            lambda x: (jax.lax.psum(x.astype(jnp.float32), "pod")
                       / n_pod).astype(x.dtype), t)
        return adamw.TrainState(avg(state.params), avg(state.m),
                                avg(state.v), state.step), losses.mean()

    def window_step(state, batches):
        bspec = jax.tree.map(lambda _: P(None, "pod"), batches)
        sspec = jax.tree.map(lambda _: P(), state)
        return jax.shard_map(local_window, mesh=mesh,
                             in_specs=(sspec, bspec),
                             out_specs=(sspec, P()),
                             axis_names={"pod"},
                             check_vma=False)(state, batches)

    return window_step


class VmappedWorkers:
    """n_workers as an array axis on one device — the testable equivalent."""

    def __init__(self, cfg, opt: adamw.AdamWConfig, lease: LeaseConfig,
                 n_workers: int, key):
        self.cfg, self.opt, self.lease = cfg, opt, lease
        self.n = n_workers
        p0 = M.init_model(cfg, key)
        rep = lambda x: jnp.broadcast_to(x[None], (n_workers,) + x.shape)
        self.state = adamw.TrainState(
            params=jax.tree.map(rep, p0),
            m=jax.tree.map(lambda x: jnp.zeros((n_workers,) + x.shape,
                                               cfg.policy.moment_dtype), p0),
            v=jax.tree.map(lambda x: jnp.zeros((n_workers,) + x.shape,
                                               cfg.policy.moment_dtype), p0),
            step=jnp.zeros((n_workers,), jnp.int32))
        self.clock = LeaseClock()
        self.local_steps = 0
        self.collective_bytes = 0         # accounting for the lease claim

        def one(state, batch):
            def lf(params):
                return M.loss_fn(cfg, params, batch)[0]
            loss, grads = jax.value_and_grad(lf)(state.params)
            return adamw.apply_updates(opt, state, grads), loss

        self._local = jax.jit(jax.vmap(one))

        def sync(state):
            avg = lambda t: jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x.astype(jnp.float32).mean(0, keepdims=True),
                    x.shape).astype(x.dtype), t)
            return adamw.TrainState(avg(state.params), avg(state.m),
                                    avg(state.v), state.step)

        self._sync = jax.jit(sync)

    def step(self, batches) -> float:
        """batches: per-worker batch dict with leading [n_workers] dim."""
        self.state, loss = self._local(self.state, batches)
        self.local_steps += 1
        if self.local_steps % self.lease.wr_lease == 0:
            self.state = self._sync(self.state)
            self.clock.on_sync(self.lease.wr_lease)
            self.collective_bytes += sum(
                x.nbytes // self.n for x in jax.tree.leaves(self.state.params))
        return float(loss.mean())
